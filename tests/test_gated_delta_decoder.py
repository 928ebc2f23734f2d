"""models/gated_delta_decoder.py (Qwen3-Next's hybrid: Gated DeltaNet mixers
and gated grouped-query attention 3 : 1, a share of a softmax-routed expert
block with a gated shared expert after every mixer, an untied head) against
its plain reference, benchmark/configs/qwen3-next-80b-a3b.reference.py, at
tiny sizes on the CPU: the loss and every parameter's gradient at the
rehearsal's period (three Gated DeltaNet layers and one of gated attention,
recomputed) and on a two-layer model without recomputation; every `assumed`
rule of the configuration flipped once in the reference, judged as the
benchmark's first step is, and refused; the tie of the share to the model
(4 shares of 16 routed experts, the shared expert with its gate counted
once, add up to the uncut expert block); the parameter count of the cell's
configuration; and the step as it lowers for a TPU (the scan's kernel pair
in its head-decay form once a Gated DeltaNet layer, no second forward, no
decay a channel and no repeated q or k)."""

import functools
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

import paddle_tpu as fluid
from benchmark.harness import manifest
from benchmark.harness import reference as harness_reference
from decoder_steps import as_one_compile, once_a_program
from paddle_tpu import models

from test_recompute_keep import _kernels, _step_for_the_tpu  # noqa: E402

CONFIG = os.path.join(REPO, "benchmark", "configs", "qwen3-next-80b-a3b")
REF = manifest.load_py(CONFIG + ".reference.py")
FILE = manifest.read_json(CONFIG + ".json")
LIMITS = FILE["rehearsal"]["reference"]
TINY = dict(vocab_size=48, max_length=64, d_model=32, linear_key_heads=2,
            linear_value_heads=4, linear_head_dim=8, n_head=4, n_kv_head=2,
            head_dim=16, rotary_dim=4, n_routed_experts=16, experts_held=4,
            expert_offset=4, top_k=3, d_expert=24, d_shared_expert=24)
RTOL, ATOL = 2e-4, 2e-5


def _ref_cfg(cfg: models.GatedDeltaDecoderConfig, **over) -> dict:
    return {"hidden_size": cfg.d_model, "hidden_act": "silu",
            "tie_word_embeddings": False,
            "num_hidden_layers": cfg.n_layer,
            "full_attention_interval": cfg.full_attention_interval,
            "linear_num_key_heads": cfg.linear_key_heads,
            "linear_num_value_heads": cfg.linear_value_heads,
            "linear_key_head_dim": cfg.linear_head_dim,
            "linear_value_head_dim": cfg.linear_head_dim,
            "num_attention_heads": cfg.n_head,
            "num_key_value_heads": cfg.n_kv_head, "head_dim": cfg.head_dim,
            "partial_rotary_factor": cfg.rotary_dim / cfg.head_dim,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.rms_norm_eps,
            "router_experts": cfg.n_routed_experts,
            "num_experts": cfg.experts_held,
            "expert_offset": cfg.expert_offset,
            "num_experts_per_tok": cfg.top_k,
            "norm_topk_prob": cfg.norm_topk_prob,
            "train_router": cfg.train_router,
            "reference": {"query_block": 16, "state_block": 16,
                          "key_head_block": 1, "expert_block": 32}, **over}


def _moved(name, v, rng, keys):
    """A parameter off its start, so that what a rule reads shows; layer
    0's q~ and k~ so small that the 1e-6 under the unit norm's root is half
    of what stands there."""
    if name == "l0_gdn_qkvz_w":
        return np.concatenate([v[:, :2 * keys] * 0.015, v[:, 2 * keys:] * 4],
                              axis=1)
    if name.endswith("_w") and "conv" not in name:
        return v * 4
    if name.endswith(("_scale", "_dt_bias", "_a_log")):
        return v + 0.3 * rng.standard_normal(v.shape)
    return v


@once_a_program
def _built(rows=2, **over):
    """(spec, params, batch, gradients, loss) of one forward-backward pass
    of a tiny model through the Executor."""
    fluid.reset_default_env()
    cfg = models.GatedDeltaDecoderConfig(**{**TINY, **over})
    spec = models.gated_delta_decoder(cfg)
    pairs = fluid.append_backward(spec.loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    scope = fluid.global_scope()
    rng = np.random.default_rng(11)
    keys = cfg.linear_key_heads * cfg.linear_head_dim
    for p in fluid.default_main_program().all_parameters():
        v = np.asarray(scope.find_var(p.name))
        scope.set_var(p.name,
                      _moved(p.name, v, rng, keys).astype(np.float32))
    params = {p.name: np.asarray(scope.find_var(p.name))
              for p in fluid.default_main_program().all_parameters()}
    batch = spec.synthetic_batch(rows, seed=5)
    got = exe.run(feed=batch, fetch_list=[spec.loss] + [g for _, g in pairs])
    grads = {p.name: np.asarray(g) for (p, _), g in zip(pairs, got[1:])}
    return spec, params, batch, grads, float(np.ravel(got[0])[0])


# ---------------------------------------------------------------------------
# every `assumed` rule of the configuration, flipped once in the reference
# ---------------------------------------------------------------------------
def _sigmoid_gate(o, z, w, eps):
    return REF._rms(o, w, eps) * jax.nn.sigmoid(z)


def _gate_then_norm(o, z, w, eps):
    return REF._rms(o * jax.nn.silu(z), w, eps)


def _decay_after(m, x):
    """The correction reads the state before its decay."""
    q_t, k_t, v_t, g_t, b_t = x
    lacking = v_t - jnp.sum(m * k_t[..., None], axis=1)
    m = jnp.exp(g_t)[:, None, None] * m \
        + b_t[:, None, None] * k_t[..., None] * lacking[:, None, :]
    return m, jnp.sum(m * q_t[..., None], axis=1)


_TURNED, _GATES = REF._turned, REF._gates


def _interleaved(x, cfg):
    """The rotary's pairs (x[2i], x[2i + 1]) where the model has (x[i],
    x[i + r / 2])."""
    r = int(x.shape[-1] * cfg["partial_rotary_factor"])
    order = np.concatenate([np.arange(0, r, 2), np.arange(1, r, 2),
                            np.arange(r, x.shape[-1])])
    return _TURNED(x[..., order], cfg)[..., np.argsort(order)]


# name: (the reference's function that is replaced, by what)
FLIPPED = {
    "no_1e-6_under_the_root": ("_unit", lambda x: x / jnp.sqrt(
        jnp.sum(x * x, axis=-1, keepdims=True))),
    "q_and_k_not_normalised": ("_unit", lambda x: x),
    "a_sigmoid_for_the_silu_gate": ("_norm_gate", _sigmoid_gate),
    "the_gate_before_the_norm": ("_norm_gate", _gate_then_norm),
    "no_dt_bias": ("_decay", lambda a, a_log, dt: -jnp.exp(a_log)
                   * jax.nn.softplus(a)),
    "the_decay_after_the_correction": ("_token", _decay_after),
    "no_silu_after_the_convolution": ("_conv_silu", lambda x, w: sum(
        jnp.concatenate([jnp.zeros((w.shape[0] - 1, x.shape[1])), x])[
            j:j + x.shape[0]] * w[j] for j in range(w.shape[0]))),
    "a_rotary_on_the_whole_head": ("_turned", lambda x, cfg: _TURNED(
        x, {**cfg, "partial_rotary_factor": 1.0})),
    "interleaved_rotary_pairs": ("_turned", _interleaved),
    "no_shared_expert_gate": ("_shared_gate", lambda p, x, name: jnp.ones(
        (x.shape[0], 1), x.dtype)),
    "gates_not_normalised": ("_gates", lambda p, x, name, cfg: _GATES(
        p, x, name, {**cfg, "norm_topk_prob": False})),
}
# one Gated DeltaNet layer and one of gated attention, not recomputed
TWO_LAYERS = {"n_layer": 2, "full_attention_interval": 2,
              "use_recompute": False, "max_length": 32}


def _one_of(which, right, wrong):
    """`right` where the traced `which` names none of `wrong` ([(index,
    function)]), else the function it names: both in the one program."""
    def chosen(*args):
        index = sum((which == i) * n for n, (i, _) in enumerate(wrong, 1))
        return jax.lax.switch(index, [functools.partial(f, *args) for f in (
            right, *(f for _, f in wrong))])

    return chosen


@functools.lru_cache(None)
def _two_layer_step():
    """TWO_LAYERS' reference step, every rule of FLIPPED beside its own
    under ONE compile (a compile a rule was 3 s each): `which` names the
    rule that is wrong by its place in sorted(FLIPPED), -1 none."""
    spec, params, batch, grads = _built(**TWO_LAYERS)[:4]
    names = sorted(FLIPPED)

    def loss_and_grad(which, p, b):
        with pytest.MonkeyPatch.context() as mp:
            for attr in sorted({a for a, _ in FLIPPED.values()}):
                mp.setattr(REF, attr, _one_of(which, getattr(REF, attr), [
                    (names.index(n), f) for n, (a, f) in FLIPPED.items()
                    if a == attr]))
            return REF.loss_and_grad(
                p, b, _ref_cfg(spec.extras["config"]),
                tuple(spec.feed_names), frozenset(grads), 1)

    step = jax.jit(loss_and_grad)
    return lambda which: step(
        which, {k: jnp.asarray(v) for k, v in params.items()},
        {k: jnp.asarray(v) for k, v in batch.items()})


@functools.lru_cache(None)
def _reference_of(key, flipped=None):
    if dict(key) == TWO_LAYERS:
        loss, grad = _two_layer_step()(
            -1 if flipped is None else sorted(FLIPPED).index(flipped))
    else:
        spec, params, batch, grads = _built(**dict(key))[:4]
        loss, grad = as_one_compile(
            REF.loss_and_grad, params, batch, _ref_cfg(spec.extras["config"]),
            tuple(spec.feed_names), frozenset(grads), 1)
    return float(loss), {k: np.asarray(v) for k, v in grad.items()}


PROGRAMS = [{}, TWO_LAYERS]


@pytest.mark.parametrize("over", PROGRAMS,
                         ids=["the_rehearsals_period", "two_layers"])
def test_program_against_the_plain_reference(over):
    """The loss and every trainable parameter's gradient (the routers take
    none: `train_router` false).  Through the period's four layers at
    weights four times their start the chunked scans' fp32 and the token
    recurrence part by a few 1e-4 of a gradient's largest entry (layer 0's
    unit norm divides by sqrt(2e-6): rounding times 700)."""
    deep = 20 if not over else 1
    spec, params, _, grads, loss = _built(**over)
    assert np.isfinite(loss) and 2.0 < loss < 8.0
    ref_loss, ref_grads = _reference_of(tuple(sorted(over.items())))
    assert loss == pytest.approx(ref_loss, rel=RTOL)
    routers = {n for n in params if n.endswith("_router_w")}
    assert len(routers) == spec.extras["config"].n_layer
    assert set(grads) == set(ref_grads) == set(params) - routers
    for name in sorted(ref_grads):
        scale = max(np.abs(ref_grads[name]).max(), 1.0)
        np.testing.assert_allclose(grads[name], ref_grads[name],
                                   rtol=RTOL * deep, atol=ATOL * deep * scale,
                                   err_msg=name)
        assert np.abs(ref_grads[name]).max() > 0, name


@pytest.mark.parametrize(
    "flipped", [None] + sorted([*FLIPPED, "the_router_trained"]))
def test_each_assumed_rule_bites(flipped):
    """The reference with one rule of the configuration's `assumed` turned
    into its neighbour, judged as the benchmark's first step is
    (benchmark/harness/reference.py) at the limits of the configuration's
    rehearsal: refused, where the reference itself passes."""
    grads, loss = _built(**TWO_LAYERS)[3:5]
    key = tuple(sorted(TWO_LAYERS.items()))
    if flipped == "the_router_trained":
        # a gradient the program does not have: the rule shows as a name
        ref = _reference_of(key)[1]
        assert not any(n.endswith("_router_w") for n in ref)
        return
    ref_loss, ref = _reference_of(key, flipped)
    prods = {k: (float(np.vdot(grads[k], ref[k])),
                 float(np.vdot(grads[k], grads[k])),
                 float(np.vdot(ref[k], ref[k]))) for k in ref}
    found = harness_reference.problems(
        harness_reference.judge(loss, ref_loss, prods), LIMITS)
    assert bool(found) == (flipped is not None), (flipped, found)


# ---------------------------------------------------------------------------
# the tie of the share to the model
# ---------------------------------------------------------------------------
SHARES, ROUTED = 4, 64


def test_four_shares_of_16_experts_add_up_to_the_uncut_expert_block():
    """What a chip of the group computes is its term of the uncut block:
    the gates are normalised over all the chosen, held or not, and a share
    adds its held experts' terms; the shared expert with its gate is whole
    on every chip and counted ONCE."""
    rng = np.random.default_rng(3)
    d, f, held = 32, 24, ROUTED // SHARES
    cfg = {"router_experts": ROUTED, "num_experts": ROUTED,
           "expert_offset": 0, "num_experts_per_tok": 10,
           "norm_topk_prob": True, "train_router": False}
    shapes = {"l0_router_w": (d, ROUTED), "l0_experts_gate_w": (ROUTED, d, f),
              "l0_experts_up_w": (ROUTED, d, f),
              "l0_experts_down_w": (ROUTED, f, d),
              "l0_shared_gate_w": (d, f), "l0_shared_up_w": (d, f),
              "l0_shared_down_w": (f, d), "l0_shared_expert_gate_w": (d, 1)}
    p = {k: jnp.asarray(rng.standard_normal(s) * 0.3, jnp.float32)
         for k, s in shapes.items()}
    x = jnp.asarray(rng.standard_normal((40, d)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = REF._expert_block(p, x, "l0", cfg)
        shared = REF._shared_gate(p, x, "l0") * REF._mlp(p, x, "l0_shared")
        assert float(jnp.abs(shared).max()) > 0.01
        terms = []
        for k in range(SHARES):
            cut = slice(k * held, (k + 1) * held)
            pk = {**p, **{n: p[n][cut] for n in (
                "l0_experts_gate_w", "l0_experts_up_w",
                "l0_experts_down_w")}}
            ck = {**cfg, "num_experts": held, "expert_offset": k * held}
            terms.append(REF._expert_block(pk, x, "l0", ck) - shared)
        assert all(float(jnp.abs(t).max()) > 1e-3 for t in terms)
        np.testing.assert_allclose(sum(terms) + shared, whole, rtol=1e-4,
                                   atol=1e-5)


def test_the_cells_configuration_holds_625667136_parameters():
    """The program built (nothing run) at the cell's sizes: the count the
    configuration file states, by kind."""
    mod = manifest.load_py(CONFIG + ".py")
    mod.build(FILE, 0)
    params = fluid.default_main_program().all_parameters()
    sizes = {p.name: int(np.prod(p.shape)) for p in params}
    fluid.reset_default_env()
    total = sum(sizes.values())
    assert total == FILE["memory"]["parameters"] == 625667136

    def of(prefix):
        return sum(n for k, n in sizes.items() if k.startswith(prefix))

    assert of("l0_gdn_") == of("l2_gdn_") == 33718464
    assert of("l3_attn_") == 27263488
    blocks = of("l1_") - of("l1_gdn_") - of("l1_n")
    assert blocks == 104859648 and of("l1_n") == 4096
    assert sizes["embed"] == sizes["head_w"] == 18992 * 2048
    assert sum(n for k, n in sizes.items() if k.endswith("_router_w")) \
        == 4 * 2048 * 512


# ---------------------------------------------------------------------------
# the step for a TPU
# ---------------------------------------------------------------------------
def test_the_step_as_it_lowers_for_a_tpu():
    """At a shape that tiles (linear heads of 128, S 256): the scan's kernel
    pair once a Gated DeltaNet layer, forward AND backward (the units are
    recomputed: no second forward), in its head-decay form: `gdn.lower`
    says `decay` head and 1 key head for 2 value heads, and the step holds
    neither a decay a channel nor q or k at the value heads' width."""
    S = 256
    cfg = models.GatedDeltaDecoderConfig(
        vocab_size=64, max_length=S, d_model=128, n_layer=2,
        full_attention_interval=2, linear_key_heads=1, linear_value_heads=2,
        linear_head_dim=128, n_head=2, n_kv_head=1, head_dim=64,
        rotary_dim=16, n_routed_experts=8, experts_held=2, top_k=2,
        d_expert=128, d_shared_expert=128)
    text, spans = _step_for_the_tpu(
        models.gated_delta_decoder, cfg,
        span_names=("gdn.lower", "kda.lower", "kda.mix.lower",
                    "short_conv.lower", "attn.lower", "recurrence.lower",
                    "router.lower"))
    assert spans["kda.lower"] == []
    assert [(s["engine"], s["decay"], s["key_heads"], s["heads"], s["chunk"])
            for s in spans["gdn.lower"]] == [("pallas", "head", 1, 2, 64)]
    # what streams around the scan: the convolution of q | k | v (512
    # channels) and the SiLU-gated norm, kernels/kda_mix.py's pairs
    assert [(s["what"], s["engine"], s["channels"])
            for s in spans["short_conv.lower"] + spans["kda.mix.lower"]] \
        == [("short_conv", "pallas", 512), ("gated_norm", "pallas", 256)]
    assert [(s["kind"], s["heads"], s["kv_heads"], s["rope"])
            for s in spans["attn.lower"]] == [("full", 2, 1, "partial")]
    assert [s["recompute"] for s in spans["recurrence.lower"]] == [1, 1]
    calls = _kernels(text)
    assert calls["_fwd_kernel"] == 1 and calls["_bwd_kernel"] == 1
    # these keep nothing: a forward in the layer, one in its recomputation
    assert (calls["_short_conv_kernel"], calls["_short_conv_bwd_kernel"],
            calls["_gated_norm_kernel"], calls["_gated_norm_bwd_kernel"]) \
        == (2, 1, 2, 1)
    # g reaches the kernels by tiles, [1, 2 heads, 1 group, 2 tiles, 128],
    # q and k at their one head, [1, S, 128] bf16
    assert re.search(r"tensor<1x2x1x2x128xf32>", text)
    assert re.search(rf"tensor<1x{S}x128xbf16>", text)
