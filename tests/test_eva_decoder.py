"""models/eva_decoder.py (EvaByte's trunk: EVA attention in its chunked
form, an fp32 residual stream, norms times (1 + g), several byte-prediction
heads on one state, a share of the attention heads) against its plain
reference, benchmark/configs/evabyte-6.5b.reference.py, at tiny sizes on the
CPU: loss, logits and every named gradient; the four head shares' output-map
terms against the uncut layer; the heads' labels and masks; every wrong rule
tools/evabyte_reference_probe.py holds the chip's first step to, refused;
the step as it lowers for a TPU (two flash sites a layer, each keeping its
output and logsumexp through the layer's recomputation, no [S, S] scores);
and that `pred_heads` 1, `unit_offset` absent and `fused_attention` lower as
they did."""

import functools
import importlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

import paddle_tpu as fluid
from benchmark.harness import reference as harness_reference
from decoder_steps import as_one_compile, once_a_program
from paddle_tpu import layers, models, observability
from paddle_tpu.core import compiler
from paddle_tpu.models.looped_decoder import IGNORED_LABEL

# (the package's `eva_decoder` is the function)
eva_model = importlib.import_module("paddle_tpu.models.eva_decoder")

import evabyte_reference_probe as probe  # noqa: E402
from test_recompute_keep import _kernels, _step_for_the_tpu  # noqa: E402

# 4 windows of 16 positions, 4 chunks a window, 3 prediction heads; a
# share of 2 heads of 4 from the second on
TINY = dict(vocab_size=40, max_length=64, n_layer=2, d_model=32, d_inner=48,
            n_head=4, heads_held=2, head_offset=1, head_dim=8, window_size=16,
            chunk_size=4, pred_heads=3, rope_theta=100.0)
RTOL, ATOL = 2e-4, 2e-5
REF = probe.mutant(None)


def _ref_cfg(cfg: models.EvaDecoderConfig, query_block=24) -> dict:
    return {"num_hidden_layers": cfg.n_layer, "heads_held": cfg.heads_held,
            "head_dim": cfg.head_dim, "window_size": cfg.window_size,
            "chunk_size": cfg.chunk_size, "num_pred_heads": cfg.pred_heads,
            "vocab_size": cfg.vocab_size, "rope_theta": cfg.rope_theta,
            "rms_norm_eps": cfg.rms_norm_eps,
            "reference": {"query_block": query_block}}


def _build(rows=2, **over):
    """(spec, params, batch, gradients, loss, logits) of one
    forward-backward pass of a tiny model through the Executor: the norms'
    g moved off 0 so that an offset that is not added shows, and q, k, mu,
    phi and o with opinions, so that where a query looks, through which
    pooling and under which softmax all show in the gradient."""
    fluid.reset_default_env()
    cfg = models.EvaDecoderConfig(**{**TINY, **over})
    spec = models.eva_decoder(cfg)
    pairs = fluid.append_backward(spec.loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    scope = fluid.global_scope()
    rng = np.random.RandomState(11)
    for p in fluid.default_main_program().all_parameters():
        v = np.asarray(scope.find_var(p.name))
        if p.name.endswith("_scale"):
            v = v + 0.3 * rng.randn(*v.shape)
        elif p.name.endswith(("_attn_q_w", "_attn_k_w")):
            v = v * 40
        elif p.name.endswith(("_attn_mu", "_attn_phi")):
            v = v * 3
        elif p.name.endswith(("_attn_o_w", "_attn_v_w")):
            v = v * 30
        scope.set_var(p.name, v.astype(np.float32))
    params = {p.name: np.asarray(scope.find_var(p.name))
              for p in fluid.default_main_program().all_parameters()}
    batch = spec.synthetic_batch(rows, seed=5)
    got = exe.run(feed=batch, fetch_list=[spec.loss, spec.extras["logits"]]
                  + [g for _, g in pairs])
    grads = {p.name: np.asarray(g) for (p, _), g in zip(pairs, got[2:])}
    return (spec, params, batch, grads, float(np.ravel(got[0])[0]),
            np.asarray(got[1]))


_built = once_a_program(_build)


def _reference_loss_and_grad(spec, params, batch, trainable, ref=REF):
    loss, grad = as_one_compile(
        ref.loss_and_grad, params, batch, _ref_cfg(spec.extras["config"]),
        tuple(spec.feed_names), frozenset(trainable), 1)
    return float(loss), {k: np.asarray(v) for k, v in grad.items()}


@functools.lru_cache(None)
def _reference_of(key, wrong=None):
    spec, params, batch, grads, _, _ = _built(**dict(key))
    return _reference_loss_and_grad(spec, params, batch, grads,
                                    probe.mutant(wrong))


@pytest.mark.parametrize("over", [
    {}, {"use_recompute": False},
    {"heads_held": 4, "head_offset": 0},
    {"max_length": 56, "window_size": 24, "chunk_size": 8, "pred_heads": 8},
    {"window_size": 64}])
def test_program_against_the_plain_reference(over):
    """Loss, logits and every parameter's gradient: whole windows, a short
    last window with eight heads (the last positions' targets masked head
    by head), one window (no summary: mu and phi take no gradient)."""
    spec, params, batch, grads, loss, logits = _built(**over)
    ref_loss, ref_grads = _reference_of(tuple(sorted(over.items())))
    assert loss == pytest.approx(ref_loss, rel=RTOL)
    assert set(grads) == set(ref_grads)
    for name in sorted(ref_grads):
        scale = max(np.abs(ref_grads[name]).max(), 1.0)
        np.testing.assert_allclose(grads[name], ref_grads[name], rtol=RTOL,
                                   atol=ATOL * scale, err_msg=name)
    cfg = _ref_cfg(spec.extras["config"])
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.vmap(lambda t: REF._logits(
            {k: jnp.asarray(v) for k, v in params.items()}, t, cfg)))(
                jnp.asarray(batch[spec.feed_names[0]]))
    np.testing.assert_allclose(logits, want, rtol=RTOL, atol=2e-4)
    pooled = over.get("window_size", 16) < over.get("max_length", 64)
    for name in ("l0_attn_mu", "l1_attn_phi"):
        assert (np.abs(grads[name]).max() > 1e-6) == pooled, name


def test_the_heads_labels_and_masks():
    """Head i at position t is held to byte t + 1 + i; what lies past the
    row's end is IGNORED_LABEL, takes no loss and no gradient, and the loss
    is the mean over the targets there are."""
    ids = np.arange(10, 22)[None, :]
    lab = eva_model.shifted_labels(ids, 3)
    assert lab.shape == (1, 12, 3)
    for i in range(3):
        np.testing.assert_array_equal(lab[0, :11 - i, i], ids[0, 1 + i:])
        assert (lab[0, 11 - i:, i] == IGNORED_LABEL).all()
    spec, params, batch, _, loss, logits = _built()
    tokens, labels = (batch[n] for n in spec.feed_names)
    np.testing.assert_array_equal(
        labels, eva_model.shifted_labels(tokens, 3))
    there = labels != IGNORED_LABEL
    assert there.sum() == 2 * (3 * 64 - (1 + 2 + 3))
    z = logits.astype(np.float64)                       # [B, S, P, V]
    logp = z - np.log(np.exp(z - z.max(-1, keepdims=True)).sum(-1,
                      keepdims=True)) - z.max(-1, keepdims=True)
    ce = -np.take_along_axis(logp, np.where(there, labels, 0)[..., None],
                             -1)[..., 0]
    assert loss == pytest.approx(ce[there].mean(), rel=1e-5)


# ---------------------------------------------------------------------------
# the share of the heads
# ---------------------------------------------------------------------------
HEADS, D, DM, S = 4, 8, 32, 64


class _Block:
    """The builder's attention sublayer as a program of its own: `run`
    sets its parameters by name and runs it on the one input."""

    def __init__(self, held, x):
        fluid.reset_default_env()
        cfg = models.EvaDecoderConfig(**{**TINY, "heads_held": held,
                                         "head_offset": 0})
        self.out = eva_model._EvaBuilder(cfg).attention(
            layers.assign(x), "l0_attn")
        self.program = fluid.default_main_program()
        self.scope = fluid.global_scope()
        self.exe = fluid.Executor(fluid.CPUPlace())
        self.exe.run(fluid.default_startup_program())

    def run(self, weights):
        for p in self.program.all_parameters():
            assert tuple(p.shape) == weights[p.name].shape, p.name
            self.scope.set_var(p.name, weights[p.name])
        return np.asarray(self.exe.run(
            self.program, scope=self.scope, fetch_list=[self.out])[0])[0]


@functools.lru_cache(None)
def _uncut():
    rng = np.random.RandomState(3)
    w = {f"l0_attn_{m}_w": rng.randn(DM, HEADS * D) * 0.6 for m in "qkv"}
    w["l0_attn_o_w"] = rng.randn(HEADS * D, DM) * 0.3
    w["l0_attn_mu"], w["l0_attn_phi"] = rng.randn(2, HEADS, D)
    return ({k: v.astype(np.float32) for k, v in w.items()},
            rng.randn(1, S, DM).astype(np.float32))


def _heads(first, count):
    """The parameters of heads first .. first + count of 4: W_q's, W_k's,
    W_v's columns, mu's, phi's and W_o's rows."""
    w = _uncut()[0]
    out = {k: w[k].reshape(DM, HEADS, D)[:, first:first + count].reshape(
        DM, -1) for k in ("l0_attn_q_w", "l0_attn_k_w", "l0_attn_v_w")}
    out["l0_attn_o_w"] = w["l0_attn_o_w"].reshape(HEADS, D, DM)[
        first:first + count].reshape(-1, DM)
    for k in ("l0_attn_mu", "l0_attn_phi"):
        out[k] = w[k][first:first + count]
    return out


@functools.lru_cache(None)
def _program(held):
    return _Block(held, _uncut()[1])


def test_the_four_head_shares_add_up_to_the_uncut_layer():
    """Each of the four chips' terms of the output map (one head each at
    this size), added, is the attention of all four heads; and a share is
    what the plain reference computes given the same share."""
    whole = _program(HEADS).run(_heads(0, HEADS))
    shares = [_program(1).run(_heads(i, 1)) for i in range(HEADS)]
    assert all(np.abs(s).max() > 0.1 for s in shares)
    np.testing.assert_allclose(sum(shares), whole, rtol=1e-4, atol=1e-5)
    cfg = models.EvaDecoderConfig(**{**TINY, "heads_held": 2})
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(REF._eva(
            {k: jnp.asarray(v) for k, v in _heads(1, 2).items()},
            jnp.asarray(_uncut()[1][0]), "l0_attn", _ref_cfg(cfg)))
    np.testing.assert_allclose(shares[1] + shares[2], ref, rtol=1e-4,
                               atol=1e-5)


def test_heads_that_are_not_the_groups_are_refused():
    fluid.reset_default_env()
    with pytest.raises(ValueError, match="not among"):
        models.eva_decoder(models.EvaDecoderConfig(
            **{**TINY, "heads_held": 3, "head_offset": 2}))


# ---------------------------------------------------------------------------
# the wrong rules
# ---------------------------------------------------------------------------
LIMITS = {"loss_rtol": 1e-4, "grad_cos_min": 0.9999, "grad_norm_rtol": 1e-4,
          "param_norm_factor": 1.01}       # the rehearsal's


def _held_to(wrong):
    spec, params, batch, grads, loss, _ = _built()
    ref_loss, ref_grads = _reference_of((), wrong)
    prods = {k: tuple(float(np.vdot(a.astype(np.float64),
                                    b.astype(np.float64)))
                      for a, b in ((grads[k], ref_grads[k]),
                                   (grads[k], grads[k]),
                                   (ref_grads[k], ref_grads[k])))
             for k in ref_grads}
    found = harness_reference.judge(loss, ref_loss, prods)
    return harness_reference.problems(found, LIMITS)


def test_the_reference_itself_passes_the_rehearsals_limits():
    assert _held_to(None) == []


@pytest.mark.parametrize("wrong", probe.MUTANTS)
def test_every_wrong_rule_is_refused(wrong):
    assert _held_to(wrong), wrong


# ---------------------------------------------------------------------------
# the step as it lowers for a TPU
# ---------------------------------------------------------------------------
# a window of 512 at head 128: the flash backward is the Pallas kernel for
# the window's call (512 x 512) and the summaries' (512 x 384)
FLASH = dict(vocab_size=64, max_length=2048, n_layer=2, d_model=64,
             d_inner=96, n_head=4, heads_held=2, head_dim=128,
             window_size=512, chunk_size=4, pred_heads=2)


SPANS = ("recurrence.lower", "eva.lower", "flash.plan", "flash.bwd_plan")


@pytest.fixture(scope="module")
def tpu_steps():
    """The step for the TPU as the tree lowers it, and with `rematerialised`
    put back to the bare checkpoint (tests/test_recompute_keep.py's
    lowering: no chip, no compiler)."""
    cfg = models.EvaDecoderConfig(**FLASH)
    steps = {"kept": _step_for_the_tpu(models.eva_decoder, cfg,
                                       span_names=SPANS)}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(compiler, "rematerialised",
                   lambda fn, **kw: jax.checkpoint(fn, **kw))
        steps["bare"] = _step_for_the_tpu(models.eva_decoder, cfg,
                                          span_names=SPANS)
    return steps


def test_two_flash_sites_a_layer_keep_out_and_lse_and_run_one_forward(
        tpu_steps):
    """A layer's EVA is two flash calls (the window's own keys, the
    summaries before it): under the recomputed layer each keeps its output
    and logsumexp, so the step holds 2 forward kernels a layer where the
    bare jax.checkpoint holds 4; the backward kernels are the same."""
    text, spans = tpu_steps["kept"]
    n = FLASH["n_layer"]
    assert [(s["recompute"], s["kept"]) for s in spans["recurrence.lower"]] \
        == n * [(1, 4)]
    kept, bare = _kernels(text), _kernels(tpu_steps["bare"][0])
    assert kept == {"_flash_kernel": 2 * n, "_flash_bwd_kernel": 2 * n}
    assert bare == {"_flash_kernel": 4 * n, "_flash_bwd_kernel": 2 * n}


def test_eva_lower_says_what_a_site_was_given(tpu_steps):
    _, spans = tpu_steps["kept"]
    sites = spans["eva.lower"]
    assert len(sites) >= FLASH["n_layer"]
    rows = 2 * 4                      # held heads x windows, one sequence
    for s in sites:
        assert (s["engine"], s["windows"], s["chunks"], s["heads_held"],
                s["window"], s["chunk"], s["sq"]) == (
            "flash", 4, 384, 2, 512, 4, 2048)
        assert s["window_pairs"] == 4 * 512 * 513 // 2
        assert s["summary_pairs"] == 128 * 512 * (0 + 1 + 2 + 3)
        assert s["pooled_bytes"] == 2 * 2 * 1536 * 128 * 2 * 5 // 4
        assert s["kept"] == "out,lse,out,lse"
        assert s["kept_bytes"] == 2 * rows * 512 * (128 * 2 + 4)
    # the two calls' plans: a window folded into the batch-head axis, and
    # the same queries over the pooled chunks; no call sees 2048 rows
    shapes = {(p["sq"], p["sk"], p["causal"]) for p in spans["flash.plan"]}
    assert shapes == {(512, 512, 1), (512, 384, 0)}
    assert {(p["sq"], p["sk"], p["engine"]) for p in spans["flash.bwd_plan"]} \
        == {(512, 512, "pallas"), (512, 384, "pallas")}


def test_no_score_array_of_the_sequences_length_in_the_step(tpu_steps):
    """No tensor of the step for the TPU is [.., S, S] or [.., S, S / c]:
    the scores live in the kernels' VMEM blocks."""
    text, _ = tpu_steps["kept"]
    S, c = FLASH["max_length"], FLASH["chunk_size"]
    shapes = set(re.findall(r"tensor<([0-9x]+)x(?:f32|bf16)>", text))
    for shape in shapes:
        dims = [int(d) for d in shape.split("x")]
        assert not (len(dims) >= 2 and dims[-2] in (S, 512)
                    and dims[-1] in (S, S // c, 1536 // c, 512)), shape


def test_on_the_cpu_the_engine_is_jax_numpy_and_nothing_is_kept():
    observability.reset()
    fluid.flags._VALUES["FLAGS_observability"] = True
    try:
        _build(rows=1, max_length=32)
        spans = [dict(s.args) for s in observability.default_tracer().spans()
                 if s.name == "eva.lower"]
    finally:
        fluid.flags._VALUES["FLAGS_observability"] = False
        observability.reset()
    assert spans and all((s["engine"], s["kept"], s["kept_bytes"]) ==
                         ("xla", "", 0) for s in spans)


def test_under_the_keep_tier_the_logits_are_the_fp32_accumulator():
    """`fp32_logits`: under bf16 compute with kept outputs every other
    product hands out bf16; the head's hands out its fp32 accumulator, not
    rounded (values bf16 cannot hold), and the stream stays fp32."""
    import jax.numpy as jnp

    from paddle_tpu.core import amp

    fluid.reset_default_env()
    amp.enable_amp("bfloat16", keep_output=True)
    try:
        spec = models.eva_decoder(models.EvaDecoderConfig(**TINY))
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        logits, = exe.run(feed=spec.synthetic_batch(1, seed=3),
                          fetch_list=[spec.extras["logits"]],
                          return_numpy=False)
    finally:
        amp.reset_amp()
    logits = jnp.asarray(logits)
    assert logits.dtype == jnp.float32
    rounded = logits.astype(jnp.bfloat16).astype(jnp.float32)
    assert float(jnp.mean(rounded != logits)) > 0.9


# ---------------------------------------------------------------------------
# what the PR's three options leave alone
# ---------------------------------------------------------------------------
def test_absent_options_build_the_programs_they_built():
    """`pred_heads` 1 (a model without the key), `unit_offset` absent and
    `fused_attention` build what they built: the head is one product over
    [.., vocabulary] with labels [B, S] and a `mean`, no 5-D reshape, no
    comparison op; `rms_norm` carries no `unit_offset` attribute and its
    scale starts at 1; under `unit_offset` the attribute is there and the
    scale starts at 0."""
    fluid.reset_default_env()
    spec = models.looped_decoder(models.LoopedDecoderConfig(
        vocab_size=32, max_length=16, n_layer=1, n_head=2, head_dim=8,
        d_model=16, d_inner=24, loop_steps=1, exit_gate=False))
    ops = fluid.default_main_program().global_block().desc.ops
    types = [op.type for op in ops]
    assert "not_equal" not in types and "elementwise_div" not in types
    assert types.count("mean") == 1 and "eva_attention" not in types
    assert spec.extras["logits"].shape[-1] == 32
    assert all(op.attr("out_dtype") is None for op in ops
               if op.type == "matmul")
    norms = [op for block in fluid.default_main_program().desc.blocks
             for op in block.ops if op.type == "rms_norm"]
    assert norms and all(op.attr("unit_offset") is None for op in norms)

    fluid.reset_default_env()
    x = layers.data("x", [4, 8], dtype="float32")
    plain = layers.rms_norm(x, param_attr=fluid.ParamAttr(name="a"))
    offset = layers.rms_norm(x, param_attr=fluid.ParamAttr(name="b"),
                             unit_offset=True)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    scope = fluid.global_scope()
    assert np.asarray(scope.find_var("a")).tolist() == [1.0] * 8
    assert np.asarray(scope.find_var("b")).tolist() == [0.0] * 8
    feed = {"x": np.random.RandomState(0).randn(2, 4, 8).astype(np.float32)}
    a, b = exe.run(feed=feed, fetch_list=[plain, offset])
    np.testing.assert_allclose(a, b, rtol=1e-6)      # 1 * y == (1 + 0) * y
    ops = {op.output("Y")[0]: op for op in
           fluid.default_main_program().global_block().desc.ops
           if op.type == "rms_norm"}
    assert ops[plain.name].attr("unit_offset") is None
    assert ops[offset.name].attr("unit_offset") is True
