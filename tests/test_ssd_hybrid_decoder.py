"""models/ssd_hybrid_decoder.py (IBM Granite 4.0-H's hybrid: Mamba-2 mixers
and grouped-query attention 9:1, a gated MLP after every mixer, constant
multipliers on the stream, the scores and the logits, a tied head) against
its plain reference, benchmark/configs/granite-4.0-h-micro.reference.py, at
tiny sizes on the CPU: the loss and every parameter's gradient at the
rehearsal's depth (Mamba-2, attention, Mamba-2) and on a ragged
row with two groups and no recomputation; each of the four multipliers shown to bite by a reference that drops it; the
tie of the share to the model (two shares of a Mamba-2 layer and of an
attention layer add up to the uncut layer); the step as it lowers for a
TPU (the scan's kernel pair once a Mamba-2 layer, no second forward); and
what `layers.kept` holds through a layer's recomputation, every MLP's first
product and every Mamba-2 mixer's in-projection (decoder_steps.py's cases:
the untagged program's loss and gradients bit for bit, each product once a
layer in the TPU's step where the untagged one has two, bf16 kept bf16, no
operation without recomputation)."""

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
from decoder_steps import (
    as_one_compile, kept_adds_no_operation_without_recompute,
    kept_is_the_untagged_program_bit_for_bit, kept_products_are_lowered_once,
    once_a_program)
from paddle_tpu import models

from test_recompute_keep import _kernels, _step_for_the_tpu  # noqa: E402

CONFIG = os.path.join(REPO, "benchmark", "configs", "granite-4.0-h-micro")
REF = manifest.load_py(CONFIG + ".reference.py")
LIMITS = manifest.read_json(CONFIG + ".json")["rehearsal"]["reference"]
TINY = dict(vocab_size=40, max_length=48, d_model=32, d_inner=48,
            ssm_heads=4, ssm_head_dim=8, d_state=8, n_head=4, n_kv_head=2,
            head_dim=8)
RTOL, ATOL = 2e-4, 2e-5
# what every other decoder here does where this one has a constant
ORDINARY = {"embedding_multiplier": 1.0, "residual_multiplier": 1.0,
            "attention_multiplier": TINY["head_dim"] ** -0.5,
            "logits_scaling": 1.0}


def _ref_cfg(cfg: models.SsdHybridDecoderConfig, **over) -> dict:
    return {"hidden_size": cfg.d_model,
            "shared_intermediate_size": cfg.d_inner,
            "layer_types": list(cfg.layer_types),
            "num_hidden_layers": cfg.n_layer,
            "mamba_heads_held": cfg.ssm_heads,
            "mamba_d_head": cfg.ssm_head_dim,
            "mamba_d_state": cfg.d_state, "mamba_n_groups": cfg.n_groups,
            "attention_heads_held": cfg.n_head,
            "key_value_heads_held": cfg.n_kv_head,
            "embedding_multiplier": cfg.embedding_multiplier,
            "residual_multiplier": cfg.residual_multiplier,
            "attention_multiplier": cfg.attention_multiplier,
            "logits_scaling": cfg.logits_scaling,
            "rms_norm_eps": cfg.rms_norm_eps,
            "reference": {"query_block": 20, "scan_block": 16,
                          "head_block": 20}, **over}


def _moved(name, v, rng):
    """A parameter off its start, so that what a rule reads shows."""
    if name.endswith("_w") and "conv" not in name:
        return v * 4
    if name.endswith(("_scale", "_d", "_dt_b", "_a_log", "_conv_b")):
        return v + 0.3 * rng.standard_normal(v.shape)
    return v


@once_a_program
def _built(rows=2, **over):
    """(spec, params, batch, gradients, loss) of one forward-backward pass
    of a tiny model through the Executor."""
    fluid.reset_default_env()
    cfg = models.SsdHybridDecoderConfig(**{**TINY, **over})
    spec = models.ssd_hybrid_decoder(cfg)
    pairs = fluid.append_backward(spec.loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    scope = fluid.global_scope()
    rng = np.random.default_rng(11)
    for p in fluid.default_main_program().all_parameters():
        v = np.asarray(scope.find_var(p.name))
        scope.set_var(p.name, _moved(p.name, v, rng).astype(np.float32))
    params = {p.name: np.asarray(scope.find_var(p.name))
              for p in fluid.default_main_program().all_parameters()}
    batch = spec.synthetic_batch(rows, seed=5)
    got = exe.run(feed=batch, fetch_list=[spec.loss] + [g for _, g in pairs])
    grads = {p.name: np.asarray(g) for (p, _), g in zip(pairs, got[1:])}
    return spec, params, batch, grads, float(np.ravel(got[0])[0])


@functools.lru_cache(None)
def _reference_of(key, dropped=None):
    spec, params, batch, grads = _built(**dict(key))[:4]
    over = {dropped: ORDINARY[dropped]} if dropped else {}
    loss, grad = as_one_compile(
        REF.loss_and_grad, params, batch,
        _ref_cfg(spec.extras["config"], **over), tuple(spec.feed_names),
        frozenset(grads), 1)
    return float(loss), {k: np.asarray(v) for k, v in grad.items()}


# the rehearsal's depth (Mamba-2, attention, Mamba-2, recomputed); two groups, no recomputation, a row that is not whole chunks
# of the scan nor whole blocks of queries
REHEARSAL = {"layer_types": ("mamba", "attention", "mamba")}
RAGGED = {"layer_types": ("mamba", "attention", "mamba"), "n_groups": 2,
          "use_recompute": False, "max_length": 37}
PROGRAMS = [REHEARSAL, RAGGED]
_ids = dict(ids=["the_rehearsal", "two_groups_ragged"])


@pytest.mark.parametrize("over", PROGRAMS, **_ids)
def test_program_against_the_plain_reference(over):
    """The loss and every parameter's gradient."""
    spec, params, _, grads, loss = _built(**over)
    assert np.isfinite(loss) and 2.0 < loss < 8.0
    assert spec.extras["config"].n_layer == len(over["layer_types"])
    ref_loss, ref_grads = _reference_of(tuple(sorted(over.items())))
    assert loss == pytest.approx(ref_loss, rel=RTOL)
    assert set(grads) == set(ref_grads) == set(params)
    for name in sorted(ref_grads):
        scale = max(np.abs(ref_grads[name]).max(), 1.0)
        np.testing.assert_allclose(grads[name], ref_grads[name], rtol=RTOL,
                                   atol=ATOL * scale, err_msg=name)
        assert np.abs(ref_grads[name]).max() > 0, name


@pytest.mark.parametrize("dropped", [None] + sorted(ORDINARY))
def test_each_multiplier_bites(dropped):
    """The reference with one constant replaced by what every other decoder
    does (no scaling of the embedding, the residual writes or the logits;
    scores times D^-1/2), judged as the benchmark's first step is
    (benchmark/harness/reference.py) at the limits of the configuration's
    rehearsal: refused, where the reference itself passes."""
    grads, loss = _built(**RAGGED)[3:5]
    ref_loss, ref = _reference_of(tuple(sorted(RAGGED.items())), dropped)
    prods = {k: (float(np.vdot(grads[k], ref[k])),
                 float(np.vdot(grads[k], grads[k])),
                 float(np.vdot(ref[k], ref[k]))) for k in ref}
    found = harness_reference.problems(
        harness_reference.judge(loss, ref_loss, prods), LIMITS)
    assert bool(found) == (dropped is not None), (dropped, found)


# ---------------------------------------------------------------------------
# the tie of the share to the model
# ---------------------------------------------------------------------------
WHOLE = dict(hidden_size=32, shared_intermediate_size=48, mamba_heads_held=8,
             mamba_d_head=8, mamba_d_state=8, mamba_n_groups=1,
             attention_heads_held=4, key_value_heads_held=2,
             residual_multiplier=0.22, attention_multiplier=0.25,
             rms_norm_eps=1e-5,
             reference={"query_block": 16, "scan_block": 16,
                        "head_block": 20})
SHARES = 2


def _whole_layer(rng):
    """The parameters of one UNCUT layer of each kind, named as layer 0."""
    d, F, D = WHOLE["hidden_size"], WHOLE["shared_intermediate_size"], 8
    H, P, N = (WHOLE["mamba_heads_held"], WHOLE["mamba_d_head"],
               WHOLE["mamba_d_state"])
    E, Hq, Hk = H * P, WHOLE["attention_heads_held"], \
        WHOLE["key_value_heads_held"]
    shapes = {
        "l0_n1_scale": (d,), "l0_n2_scale": (d,),
        "l0_mlp_1_w": (d, 2 * F), "l0_mlp_2_w": (F, d),
        "l0_ssm_in_w": (d, 2 * E + 2 * N + H),
        "l0_ssm_conv_w": (4, E + 2 * N), "l0_ssm_conv_b": (E + 2 * N,),
        "l0_ssm_a_log": (H,), "l0_ssm_d": (H,), "l0_ssm_dt_b": (H,),
        "l0_ssm_norm_scale": (E,), "l0_ssm_out_w": (E, d),
        "l0_attn_q_w": (d, Hq * D), "l0_attn_k_w": (d, Hk * D),
        "l0_attn_v_w": (d, Hk * D), "l0_attn_o_w": (Hq * D, d)}
    return {k: jnp.asarray(rng.standard_normal(s) * (0.3 if k.endswith("_w")
                                                     else 1.0), jnp.float32)
            for k, s in shapes.items()}


def _share(p, k):
    """(share k's parameters of the uncut layer's, its configuration): the
    state-space heads, the query heads and the key/value heads by
    contiguous halves; B, C, the norms and the MLP whole."""
    H, P, N = (WHOLE["mamba_heads_held"], WHOLE["mamba_d_head"],
               WHOLE["mamba_d_state"])
    E, Hq, Hk, D = (H * P, WHOLE["attention_heads_held"],
                    WHOLE["key_value_heads_held"], 8)
    heads = np.arange(k * H // SHARES, (k + 1) * H // SHARES)
    chans = (heads[:, None] * P + np.arange(P)).ravel()
    conv = np.concatenate([chans, E + np.arange(2 * N)])
    into = np.concatenate([chans, E + conv, 2 * E + 2 * N + heads])
    q = np.arange(k * Hq * D // SHARES, (k + 1) * Hq * D // SHARES)
    kv = np.arange(k * Hk * D // SHARES, (k + 1) * Hk * D // SHARES)
    cut = {"l0_ssm_in_w": p["l0_ssm_in_w"][:, into],
           "l0_ssm_conv_w": p["l0_ssm_conv_w"][:, conv],
           "l0_ssm_conv_b": p["l0_ssm_conv_b"][conv],
           "l0_ssm_a_log": p["l0_ssm_a_log"][heads],
           "l0_ssm_d": p["l0_ssm_d"][heads],
           "l0_ssm_dt_b": p["l0_ssm_dt_b"][heads],
           "l0_ssm_norm_scale": p["l0_ssm_norm_scale"][chans],
           "l0_ssm_out_w": p["l0_ssm_out_w"][chans],
           "l0_attn_q_w": p["l0_attn_q_w"][:, q],
           "l0_attn_k_w": p["l0_attn_k_w"][:, kv],
           "l0_attn_v_w": p["l0_attn_v_w"][:, kv],
           "l0_attn_o_w": p["l0_attn_o_w"][q]}
    return {**p, **cut}, {**WHOLE, "mamba_heads_held": H // SHARES,
                          "attention_heads_held": Hq // SHARES,
                          "key_value_heads_held": Hk // SHARES}


@pytest.mark.parametrize("kind", ["mamba", "attention"])
def test_two_shares_add_up_to_the_uncut_layer(kind):
    """What a chip of the group of 2 computes is its term of the uncut
    layer.  An attention share's output is its heads' term of W_o's sum.  A
    Mamba-2 share norms by the mean square of ITS channels, so its output
    is its term times rms_whole / rms_share a token: the one number a token
    the deployment's all-reduce would carry (the sum of squares), computed
    here from the reference.  The norms and the MLP are counted once."""
    rng = np.random.default_rng(3)
    p = _whole_layer(rng)
    h = jnp.asarray(rng.standard_normal((24, WHOLE["hidden_size"])),
                    jnp.float32)
    eps, r = WHOLE["rms_norm_eps"], WHOLE["residual_multiplier"]
    with jax.default_matmul_precision("highest"):
        u = REF._rms(h, p["l0_n1_scale"], eps)
        shares = [_share(p, k) for k in range(SHARES)]
        if kind == "attention":
            terms = [REF._attention(pk, u, "l0_attn", ck)
                     for pk, ck in shares]
            whole = REF._attention(p, u, "l0_attn", WHOLE)
        else:
            gated = [REF._mamba_gated(pk, u, "l0_ssm", ck)
                     for pk, ck in shares]
            np.testing.assert_allclose(
                jnp.concatenate(gated, axis=1),
                REF._mamba_gated(p, u, "l0_ssm", WHOLE), rtol=1e-5,
                atol=1e-6)

            def rms(g):
                return jnp.sqrt(jnp.mean(g * g, axis=1, keepdims=True) + eps)

            rms_whole = rms(jnp.concatenate(gated, axis=1))
            terms = [REF._mamba(pk, u, "l0_ssm", ck) * rms(g) / rms_whole
                     for (pk, ck), g in zip(shares, gated)]
            whole = REF._mamba(p, u, "l0_ssm", WHOLE)
        np.testing.assert_allclose(sum(terms), whole, rtol=1e-4, atol=1e-5)
        a = h + r * sum(terms)
        out = a + r * REF._mlp(p, REF._rms(a, p["l0_n2_scale"], eps),
                               "l0_mlp", WHOLE)
        np.testing.assert_allclose(
            out, REF._layer(p, h, 0, kind, WHOLE), rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# the step for a TPU
# ---------------------------------------------------------------------------
def test_the_step_as_it_lowers_for_a_tpu():
    """At a shape that tiles (heads of 64, 128 states, S 256): the scan's
    kernel pair once a Mamba-2 layer, forward AND backward (the units are
    recomputed: no second forward); no state a token anywhere in the
    step."""
    S = 256
    cfg = models.SsdHybridDecoderConfig(
        vocab_size=64, max_length=S, d_model=128, d_inner=64,
        layer_types=("mamba", "attention", "mamba"), ssm_heads=2,
        n_head=2, n_kv_head=1)
    text, spans = _step_for_the_tpu(
        models.ssd_hybrid_decoder, cfg,
        span_names=("ssd.lower", "attn.lower", "recurrence.lower"))
    # the scan's y and starts, the in-projection's output and W1's; of the
    # attention layer W1's output alone (a flash site keeps its out and lse
    # where its backward is the Pallas kernel: not at S 256)
    assert [s["kept"] for s in spans["recurrence.lower"]] == [4, 1, 4]
    calls = _kernels(text)
    assert calls["_fwd_kernel"] == 2 and calls["_bwd_kernel"] == 2
    assert [(s["engine"], s["chunk"], s["block"], s["heads"], s["states"])
            for s in spans["ssd.lower"]] == [("pallas", 256, 2, 2, 128)] * 2
    assert [(s["kind"], s["heads"], s["kv_heads"])
            for s in spans["attn.lower"]] == [("full", 2, 1)]
    assert not re.search(rf"tensor<[0-9x]*{S}x2x64x128x", text)


# ---------------------------------------------------------------------------
# what survives a layer's recomputation besides the kernels' own values
# (layers.kept): W1's output in every layer, the in-projection's (z | xBC |
# dt) in a Mamba-2 layer
# ---------------------------------------------------------------------------
KEPT = (models.ssd_hybrid_decoder, models.SsdHybridDecoderConfig)
KEPT_SIZES = {**TINY, **REHEARSAL}
W1_WIDTH = 2 * TINY["d_inner"]
IN_WIDTH = 2 * TINY["ssm_heads"] * TINY["ssm_head_dim"] \
    + 2 * TINY["d_state"] + TINY["ssm_heads"]
KEPT_A_UNIT = [2, 1, 2]


@pytest.mark.parametrize("recompute", [True, False])
def test_the_tagged_programs_loss_and_gradients_are_the_untagged_ones(
        recompute):
    """With the stream entering the first unit as the embedding wrote it
    (`embedding_multiplier` 1): the CPU compiler's algebraic simplifier
    folds that constant into the first unit's FIRST forward and cannot
    behind the recomputation's barrier, so with it the UNTAGGED step's
    recomputed first layer is not its own first forward to the bit (13 of
    70 values differ in their last bit, all of layer 0; none with the
    simplifier off, which compiles for twice as long)."""
    kept_is_the_untagged_program_bit_for_bit(
        *KEPT, tags=sum(KEPT_A_UNIT), least=30, **KEPT_SIZES,
        embedding_multiplier=1.0, use_recompute=recompute)


def test_the_kept_products_are_lowered_once_a_layer_where_the_untagged_step_has_two():
    kept_products_are_lowered_once(
        *KEPT, widths={W1_WIDTH: 3, IN_WIDTH: 2}, kept_a_unit=KEPT_A_UNIT,
        **KEPT_SIZES)


def test_without_recompute_the_tags_add_no_operation():
    kept_adds_no_operation_without_recompute(
        *KEPT, kept_a_unit=KEPT_A_UNIT, **KEPT_SIZES)
