"""models/hybrid_linear_decoder.py (Kimi-Linear-48B-A3B's language model:
Kimi Delta Attention layers and latent-attention layers without positions,
3 to 1, a sparse expert block behind a sigmoid router) against its plain
reference, benchmark/configs/kimi-linear-48b-a3b.reference.py, at tiny sizes
on the CPU; the layer kinds read from the two 1-indexed lists; latent
attention without rotary and with it as it was; every control of
tools/kimi_reference_probe.py refused; the 32 shares against the uncut
expert block; the ops kda_conv_decay and kda_gated_norm against the
composition of layers they replaced, and what their spans say."""

import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import paddle_tpu as fluid
from decoder_steps import as_one_compile, once_a_program
from benchmark.harness import manifest
from benchmark.harness import reference as harness_reference
from paddle_tpu import layers, models, observability
from paddle_tpu.core import amp
from paddle_tpu.core.framework import name_scope
from paddle_tpu.kernels import engine, kda_mix
from paddle_tpu.observability import span
from paddle_tpu.param_attr import ParamAttr
from paddle_tpu.ops import attention_ops, moe_ops

sys.path.insert(0, os.path.join(REPO, "tools"))
import kimi_reference_probe as probe  # noqa: E402

# the module (models exports the function of the same name)
hybrid = sys.modules["paddle_tpu.models.hybrid_linear_decoder"]

# the rehearsal's sizes: the dense layer and one whole period, two chunks
TINY = dict(vocab_size=64, max_length=128, n_layer=5, d_model=32, d_inner=64,
            kda_heads=2, kda_head_dim=16, n_head=2, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=24,
            n_routed_experts=16, experts_held=4, expert_offset=4, top_k=3,
            d_expert=24)
RTOL, ATOL = 2e-4, 2e-5


def _reference():
    return manifest.load_py(probe.REFERENCE)


def _ref_cfg(cfg: models.HybridLinearDecoderConfig) -> dict:
    return {
        "num_hidden_layers": cfg.n_layer, "hidden_size": cfg.d_model,
        "hidden_act": "silu", "tie_word_embeddings": False,
        "first_k_dense_replace": cfg.first_k_dense,
        "linear_attn_config": {
            "kda_layers": list(cfg.kda_layers),
            "full_attn_layers": list(cfg.full_attn_layers),
            "num_heads": cfg.kda_heads, "head_dim": cfg.kda_head_dim,
            "short_conv_kernel_size": cfg.short_conv_kernel_size},
        "num_attention_heads": cfg.n_head,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "kv_lora_rank": cfg.kv_lora_rank,
        "mla_use_nope": True, "q_lora_rank": None,
        "rms_norm_eps": cfg.rms_norm_eps,
        "moe_router_activation_func": "sigmoid", "num_expert_group": 1,
        "topk_group": 1, "num_experts_per_token": cfg.top_k,
        "moe_renormalize": cfg.norm_topk_prob,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "num_experts": cfg.experts_held,
        "router_experts": cfg.n_routed_experts,
        "expert_offset": cfg.expert_offset,
        "reference": {"query_block": 32, "state_block": 16}}


def _opinions(scope, rng):
    """Every parameter that starts where a mistake could not show, moved:
    the norms' scales off 1, the gate's and the selection's biases off 0,
    and the maps that decide where a head looks, how fast it forgets, how
    much it corrects and what it lets through made larger, so that each
    shows in the gradient."""
    for p in fluid.default_main_program().all_parameters():
        v = np.asarray(scope.find_var(p.name))
        if p.name.endswith("_scale"):
            v = v + 0.3 * rng.randn(*v.shape)
        elif p.name.endswith(("_gate_bias", "_router_bias")):
            v = v + 0.1 * rng.randn(*v.shape)
        elif p.name.endswith(("_attn_q_w", "_attn_k_w", "_attn_f_b_w",
                              "_attn_beta_w", "_attn_gate_b_w")):
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
    cfg = models.HybridLinearDecoderConfig(**{**TINY, **over})
    spec = models.hybrid_linear_decoder(cfg)
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


_built = once_a_program(_build)


def _reference_loss_and_grad(spec, params, batch, trainable, make=None):
    loss, grad = as_one_compile(
        make or _reference().loss_and_grad, params, batch,
        _ref_cfg(spec.extras["config"]), feed_names=tuple(spec.feed_names),
        trainable=frozenset(trainable), micro=1)
    return float(loss), {k: np.asarray(v) for k, v in grad.items()}


# three layers, each kind of layer and of feed-forward: the CPU compiles a
# KDA layer for ~6 s, and only the first case pays for the cell's five
SHORT = {"n_layer": 3, "kda_layers": (1, 3), "full_attn_layers": (2,),
         "max_length": 64}
# one layer of each kind (KDA over the dense feed-forward, latent attention
# over the experts) where a case or a control asks for no more: three layers
# were 22-38 s a case on the driver's run (PR 54); KDA over the experts is
# the first case's and the fourth's
PAIR = {**SHORT, "n_layer": 2, "kda_layers": (1,)}


@pytest.mark.parametrize("over", [
    {}, {**PAIR, "use_recompute": False},
    {**PAIR, "expert_offset": 0, "experts_held": 16},
    {**SHORT, "first_k_dense": 2},
    # another pattern from the same two lists' rule, another tap count
    {**PAIR, "kda_layers": (2,), "full_attn_layers": (1,),
     "short_conv_kernel_size": 2}])
def test_program_against_the_plain_reference(over):
    """Loss and every parameter's gradient, named parameter by named
    parameter: the convolutions' taps, A_log, dt_bias, the low-rank maps,
    the head norm's scale and the routers among them."""
    spec, params, batch, grads, loss = _built(**over)
    ref_loss, ref_grads = _reference_loss_and_grad(spec, params, batch, grads)
    assert loss == pytest.approx(ref_loss, rel=RTOL)
    assert set(grads) == set(ref_grads)
    cfg = spec.extras["config"]
    for i in range(cfg.n_layer):
        kda = i + 1 in cfg.kda_layers
        assert (f"l{i}_attn_a_log" in grads) == kda
        assert (f"l{i}_attn_kvb_w" in grads) == (not kda)
        for part in ("conv_q_w", "conv_k_w", "conv_v_w", "a_log", "dt_bias",
                     "f_a_w", "f_b_w", "beta_w", "gate_a_w", "gate_b_w",
                     "gate_bias", "on_scale") if kda else ():
            assert np.abs(ref_grads[f"l{i}_attn_{part}"]).max() > 0, (i, part)
    for name in sorted(ref_grads):
        scale = max(np.abs(ref_grads[name]).max(), 1.0)
        np.testing.assert_allclose(grads[name], ref_grads[name], rtol=RTOL,
                                   atol=ATOL * scale, err_msg=name)


# ---------------------------------------------------------------------------
# the layer kinds
# ---------------------------------------------------------------------------
def _mixers(**over):
    fluid.reset_default_env()
    models.hybrid_linear_decoder(models.HybridLinearDecoderConfig(
        **{**TINY, "max_length": 64, **over}))
    kinds = []
    for block in fluid.default_main_program().blocks:
        kinds += [op.type for op in block.desc.ops
                  if op.type in ("gated_delta_attention", "latent_attention")]
    return kinds


def test_the_layer_kinds_come_from_the_two_lists_numbered_from_one():
    """The published lists, kept whole, read up to the depth: layers 1, 2,
    3 and 5 are KDA and layer 4 is MLA; a deeper cut reads further."""
    published = dict(
        kda_layers=(1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21,
                    22, 23, 25, 26),
        full_attn_layers=(4, 8, 12, 16, 20, 24, 27))
    kda, mla = "gated_delta_attention", "latent_attention"
    assert _mixers(**published) == [kda, kda, kda, mla, kda]
    assert _mixers(n_layer=8, **published) == [kda, kda, kda, mla,
                                               kda, kda, kda, mla]
    assert _mixers() == [kda, kda, kda, mla, kda]       # the defaults
    with pytest.raises(ValueError, match="layer 5 is in neither"):
        _mixers(kda_layers=(1, 2, 3), full_attn_layers=(4,))


def test_the_benchmark_file_reads_the_lists_the_same_way():
    mod = manifest.load_py(os.path.join(
        REPO, "benchmark", "configs", "kimi-linear-48b-a3b.py"))
    cfg = manifest.read_json(os.path.join(
        REPO, "benchmark", "configs", "kimi-linear-48b-a3b.json"))
    assert mod.layer_kinds(cfg) == ["kda", "kda", "kda", "mla", "kda"]
    assert mod.layer_kinds({**cfg, "num_hidden_layers": 27}).count("mla") == 7
    ref = _reference()
    assert [ref._kind(i, cfg) for i in range(5)] == mod.layer_kinds(cfg)


# ---------------------------------------------------------------------------
# latent attention without rotary, and with it as it was
# ---------------------------------------------------------------------------
def _latent_attention_as_it_was(ctx, ins, attrs):
    """ops/attention_ops.py::_latent_attention before it took `rope`."""
    data, _rotate = attention_ops.data, attention_ops._rotate
    q = data(ins["Q"][0])
    latent = data(ins["Latent"][0])
    k_rope = data(ins["KRope"][0])
    kv_w = data(ins["KvUpW"][0])
    H = int(attrs["n_head"])
    dn, dr, dv = (int(attrs[a]) for a in
                  ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    base = float(attrs.get("rope_base", 10000.0))
    B, S = q.shape[0], q.shape[1]

    def heads(t):
        return jnp.swapaxes(t.reshape(B, S, H, -1), 1, 2)

    with span("mla.lower", heads=H, qk_dim=dn + dr, v_dim=dv,
              kv_rank=int(latent.shape[-1]), padded_v=0) as sp:
        lc, wc = amp.mxu_operands(latent, kv_w)
        kv = heads(amp.mxu_output(jnp.matmul(lc, wc), latent, kv_w))
        q = heads(q)
        q = jnp.concatenate([q[..., :dn], _rotate(q[..., dn:], base)], -1)
        shared = jnp.broadcast_to(_rotate(k_rope[:, None], base).astype(
            kv.dtype), (B, H, S, dr))
        k = jnp.concatenate([kv[..., :dn], shared], -1)
        q, k = amp.match_kept(q, k)
        out = attention_ops._attend(ctx, sp, q, k,
                                    kv[..., dn:].astype(k.dtype), None,
                                    True, (dn + dr) ** -0.5)
    return {"Out": [jnp.swapaxes(out, 1, 2).reshape(B, S, H * dv)]}


MLA = dict(n_head=2, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16)


def _mla_inputs(seed=0, B=2, S=12, rank=24):
    r = np.random.RandomState(seed)
    return tuple(jnp.asarray(a, jnp.float32) for a in (
        r.randn(B, S, 2 * 24), r.randn(B, S, rank), r.randn(B, S, 8),
        r.randn(rank, 2 * 32) * 0.3))


def _lowered(fn, attrs, args):
    ctx = types.SimpleNamespace(mesh=None, kept=0)
    return jax.jit(lambda q, latent, k_rope, w: fn(
        ctx, {"Q": [q], "Latent": [latent], "KRope": [k_rope],
              "KvUpW": [w]}, attrs)["Out"][0]).lower(*args).as_text()


def test_with_rotary_latent_attention_lowers_as_it_did():
    """moonlight-16b-a3b's op (no `rope` attribute, or "rotary") lowers to
    the StableHLO of the lowering as it was before the attribute; "none"
    to another."""
    args = _mla_inputs()
    attrs = {**MLA, "rope_base": 50000.0}
    was = _lowered(_latent_attention_as_it_was, attrs, args)
    assert _lowered(attention_ops._latent_attention, attrs, args) == was
    assert _lowered(attention_ops._latent_attention,
                    {**attrs, "rope": "rotary"}, args) == was
    assert _lowered(attention_ops._latent_attention,
                    {**attrs, "rope": "none"}, args) != was
    with pytest.raises(ValueError, match="neither"):
        _lowered(attention_ops._latent_attention, {**attrs, "rope": "yarn"},
                 args)


def test_the_expert_decoder_builds_the_program_it_built():
    """No `rope` attribute on moonlight-16b-a3b's latent_attention ops, a
    trainable router, and the same parameters."""
    fluid.reset_default_env()
    models.expert_decoder(models.ExpertDecoderConfig(
        vocab_size=64, max_length=16, n_layer=2, d_model=32, d_inner=64,
        n_head=2, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        kv_lora_rank=24, n_routed_experts=16, experts_held=4, top_k=3,
        d_expert=24))
    program = fluid.default_main_program()
    ops = [op for b in program.blocks for op in b.desc.ops]
    mla = [op for op in ops if op.type == "latent_attention"]
    assert len(mla) == 2 and all("rope" not in op.attrs for op in mla)
    assert not any(op.type == "detach" for op in ops)
    assert all(p.trainable for p in program.all_parameters()
               if p.name.endswith("_router_w"))


def test_without_rotary_the_scores_know_no_position():
    """rope "none": q.k over all 24 features as projected, the shared
    8-wide key part unturned, under the causal mask alone."""
    q, latent, k_rope, w = _mla_inputs(seed=3)
    fluid.reset_default_env()
    names = ("q", "latent", "k_rope", "w")
    ins = [layers.data(n, list(a.shape), append_batch_size=False)
           for n, a in zip(names, (q, latent, k_rope, w))]
    outs = [layers.latent_attention(*ins, rope_base=100.0, rope=rope, **MLA)
            for rope in ("none", "rotary")]
    ops = [op for op in fluid.default_main_program().global_block().desc.ops
           if op.type == "latent_attention"]
    assert ops[0].attrs["rope"] == "none" and "rope" not in ops[1].attrs
    got = fluid.Executor(fluid.CPUPlace()).run(
        feed=dict(zip(names, (np.asarray(a) for a in (q, latent, k_rope,
                                                      w)))),
        fetch_list=outs)
    B, S = q.shape[:2]

    def heads(t):
        return jnp.swapaxes(t.reshape(B, S, 2, -1), 1, 2)

    kv = heads(latent @ w)
    k = jnp.concatenate([kv[..., :16], jnp.broadcast_to(
        k_rope[:, None], (B, 2, S, 8))], -1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", heads(q), k) * 24 ** -0.5
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    probs = jax.nn.softmax(jnp.where(causal, scores, -1e30), axis=-1)
    want = jnp.swapaxes(jnp.einsum("bhqk,bhkd->bhqd", probs, kv[..., 16:]),
                        1, 2).reshape(B, S, 32)
    np.testing.assert_allclose(got[0], want, rtol=1e-4, atol=1e-5)
    assert np.abs(got[0] - got[1]).max() > 1e-2


# ---------------------------------------------------------------------------
# the controls
# ---------------------------------------------------------------------------
MUTANT_TOL = {"loss_rtol": 1e-4, "grad_cos_min": 0.9999,
              "grad_norm_rtol": 1e-3, "param_norm_factor": 1.01}


@pytest.fixture(scope="module")
def one_step():
    return _built(**PAIR, expert_offset=0, experts_held=16)


def _refused(step, name):
    spec, params, batch, grads, loss = step
    ref_loss, ref_grads = _reference_loss_and_grad(
        spec, params, batch, grads, make=probe.mutant(name))
    prods = {k: (float(np.vdot(grads[k], ref_grads[k])),
                 float(np.vdot(grads[k], grads[k])),
                 float(np.vdot(ref_grads[k], ref_grads[k])))
             for k in grads}
    found = harness_reference.judge(loss, ref_loss, prods)
    return harness_reference.problems(found, MUTANT_TOL), found


@pytest.mark.parametrize("name", (None,) + probe.MUTANTS)
def test_the_reference_refuses_each_control(one_step, name):
    """The program against the reference is inside the rehearsal's
    tolerances, against each control (a decay a head, beta left out, the
    correction left out, q and k not normalised, the decay after the
    correction, rotary left on, fp8 matmuls) outside at least one."""
    problems, found = _refused(one_step, name)
    assert bool(problems) == (name is not None), (name, found)


def test_the_controls_are_issue_47s_and_an_unknown_one_is_an_error():
    assert probe.MUTANTS == (
        "decay_a_head", "beta_left_out", "correction_left_out",
        "not_normalised", "decay_after_correction", "rotary_on",
        "fp8_matmuls")
    with pytest.raises(KeyError):
        probe.mutant("no_such_control")
    cfg = manifest.read_json(os.path.join(
        REPO, "benchmark", "configs", "kimi-linear-48b-a3b.json"))
    assert MUTANT_TOL == {k: v for k, v in
                          cfg["rehearsal"]["reference"].items()
                          if k in MUTANT_TOL}


# ---------------------------------------------------------------------------
# the share
# ---------------------------------------------------------------------------
def test_the_32_shares_add_up_to_the_uncut_expert_block():
    """32 shares of one expert layer at a router 32 wide, each as the
    program computes it (moe_ops.route + held_experts_part on its 1 of 32
    experts), with the shared expert counted once, are the uncut
    reference's whole block; and a share alone (the first, the last and
    one between) is the reference given the same share."""
    ref = _reference()
    rng = np.random.RandomState(3)
    d, f, experts, held, k = 32, 24, 32, 1, 8
    p = {"l1_router_w": rng.randn(d, experts) * 0.5,
         "l1_router_bias": rng.uniform(-0.2, 0.2, experts),
         "l1_experts_gate_w": rng.randn(experts, d, f) * 0.2,
         "l1_experts_up_w": rng.randn(experts, d, f) * 0.2,
         "l1_experts_down_w": rng.randn(experts, f, d) * 0.2,
         "l1_shared_gate_w": rng.randn(d, f) * 0.2,
         "l1_shared_up_w": rng.randn(d, f) * 0.2,
         "l1_shared_down_w": rng.randn(f, d) * 0.2}
    p = {n: jnp.asarray(v, jnp.float32) for n, v in p.items()}
    x = jnp.asarray(rng.randn(40, d), jnp.float32)
    cfg = {"moe_router_activation_func": "sigmoid", "num_expert_group": 1,
           "topk_group": 1, "num_experts_per_token": k,
           "moe_renormalize": True, "routed_scaling_factor": 2.446,
           "router_experts": experts}
    whole = ref._expert_block(p, x, "l1", {**cfg, "num_experts": experts,
                                           "expert_offset": 0})
    idx, weight, _ = moe_ops.route(x, p["l1_router_w"], p["l1_router_bias"],
                                   k, 2.446, True)
    shared = ref._mlp(p, x, "l1_shared")
    total = shared
    # one compile for the 32 shares: the offset is a value, not a constant
    held_part = jax.jit(moe_ops.held_experts_part, static_argnums=(7,))
    for share in range(experts // held):
        mine = slice(share * held, (share + 1) * held)
        part = held_part(
            x, idx, weight, p["l1_experts_gate_w"][mine],
            p["l1_experts_up_w"][mine], p["l1_experts_down_w"][mine],
            share * held, experts)
        total = total + part
        if share not in (0, 13, 31):    # a compile a share: three of them
            continue
        cut = {n: v[mine] if "_experts_" in n else v for n, v in p.items()}
        alone = ref._expert_block(cut, x, "l1", {
            **cfg, "num_experts": held, "expert_offset": share * held})
        np.testing.assert_allclose(part + shared, alone, rtol=1e-4,
                                   atol=1e-5)
    np.testing.assert_allclose(total, whole, rtol=1e-4, atol=1e-5)
    # every token's 8 experts lie in 8 different shares
    assert int(jnp.sum(jnp.abs(total - shared) > 0)) > 0


# ---------------------------------------------------------------------------
# the two ops around the scan
# ---------------------------------------------------------------------------
def _delta_attention_as_it_was(self, u, name):
    """models/hybrid_linear_decoder.py::delta_attention before the ops
    kda_conv_decay and kda_gated_norm: three short_conv1d, the decay from
    elementwise layers, rms_norm a head and the gate."""
    cfg = self.cfg
    H, D, d = cfg.kda_heads, cfg.kda_head_dim, cfg.d_model
    taps = cfg.short_conv_kernel_size
    projected = [self.linear(u, d, H * D, f"{name}_{p}") for p in "qkv"]
    with name_scope("kda.mix"):
        q, k, v = (layers.short_conv1d(
            t, self.conv_param([taps, H * D], f"{name}_conv_{p}_w", taps),
            activation="silu") for t, p in zip(projected, "qkv"))
        f = layers.elementwise_add(
            layers.cast(self.low_rank(u, D, H * D, f"{name}_f"), "float32"),
            self.uniform([H * D], f"{name}_dt_bias", *hybrid._DT_RANGE))
        rate = layers.scale(layers.exp(
            self.uniform([H], f"{name}_a_log", *hybrid._A_RANGE)), scale=-1.0)
        g = layers.reshape(layers.elementwise_mul(
            layers.reshape(layers.softplus(f), shape=[0, 0, H, D]),
            rate, axis=2), shape=[0, 0, H * D])
        beta = layers.sigmoid(layers.cast(
            self.linear(u, d, H, f"{name}_beta"), "float32"))
    o = layers.gated_delta_attention(q, k, v, g, beta, heads=H)
    with name_scope("kda.mix"):
        gate = layers.sigmoid(layers.elementwise_add(
            self.low_rank(u, D, H * D, f"{name}_gate"),
            self.constant([H * D], f"{name}_gate_bias", 0.0)))
        o = layers.rms_norm(
            layers.reshape(o, shape=[0, 0, H, D]), begin_norm_axis=-1,
            epsilon=cfg.rms_norm_eps,
            param_attr=ParamAttr(name=f"{name}_on_scale"))
        o = layers.elementwise_mul(
            layers.reshape(o, shape=[0, 0, H * D]), gate)
    return self.linear(o, H * D, d, f"{name}_o")


def test_the_two_ops_are_the_composition_they_replaced(one_step, monkeypatch):
    """The same parameters (names, shapes, the values they start at), the
    same loss and every gradient to fp32 rounding."""
    _, params, _, grads, loss = one_step
    monkeypatch.setattr(hybrid._HybridBuilder, "delta_attention",
                        _delta_attention_as_it_was)
    _, was_params, _, was_grads, was_loss = _build(
        **PAIR, expert_offset=0, experts_held=16)
    kinds = [op.type for b in fluid.default_main_program().blocks
             for op in b.desc.ops]
    assert "short_conv1d" in kinds and "kda_conv_decay" not in kinds
    assert set(params) == set(was_params) and set(grads) == set(was_grads)
    for name in params:
        np.testing.assert_array_equal(params[name], was_params[name], name)
    assert loss == pytest.approx(was_loss, rel=1e-6)
    for name in sorted(grads):
        scale = max(np.abs(was_grads[name]).max(), 1e-3)
        np.testing.assert_allclose(grads[name], was_grads[name], rtol=1e-4,
                                   atol=2e-6 * scale, err_msg=name)


def test_kda_mix_lower_says_pallas_at_the_cells_shape():
    """The two ops lowered (abstractly: nothing compiles) at [1, 4096, 32 x
    128] for the TPU: `engine` pallas, the tile, the halo and the working
    sets `conv_tiles` / `norm_tiles` give the shape, and the bytes the
    passes move; the same program on the CPU says xla."""
    S, H, D, taps = 4096, 32, 128, 4
    C = H * D
    shapes = dict(q=[1, S, C], k=[1, S, C], v=[1, S, C], f=[1, S, C],
                  wq=[taps, C], wk=[taps, C], wv=[taps, C], dt_bias=[C],
                  a_log=[H], gate=[1, S, C], gate_bias=[C], scale=[D])
    fluid.reset_default_env()
    ins = {n: layers.data(n, s, append_batch_size=False, dtype="float32")
           for n, s in shapes.items()}
    q, k, v, g = layers.kda_conv_decay(*(ins[n] for n in (
        "q", "k", "v", "f", "wq", "wk", "wv", "dt_bias", "a_log")), heads=H)
    out = layers.kda_gated_norm(q, ins["gate"], ins["gate_bias"],
                                ins["scale"], heads=H, epsilon=1e-5)
    assert list(g.shape) == [1, S, C] and g.dtype.name == "FP32"
    assert list(out.shape) == [1, S, C]
    feed = {n: np.zeros(s, np.float32) for n, s in shapes.items()}

    def lowered(for_the_tpu):
        observability.reset()
        with fluid.flags.tpu_trace_scope(for_the_tpu):
            compiled, *rest = fluid.Executor(
                fluid.CPUPlace()).capture_program(
                    feed=feed, fetch_list=[k, v, g, out])
            jax.eval_shape(compiled.raw_fn, *rest)
        return [dict(s.args) for s in observability.default_tracer().spans()
                if s.name == "kda.mix.lower"]

    fluid.flags._VALUES["FLAGS_observability"] = True
    try:
        on_tpu, on_cpu = lowered(True), lowered(False)
    finally:
        fluid.flags._VALUES["FLAGS_observability"] = False
        observability.reset()
        fluid.reset_default_env()
    MB = 2 ** 20
    moved = {"conv_decay": (6 * 64 + 64 + 64 + 9 * 64 + 2 * 64 + 64) * MB,
             "gated_norm": (3 * 64 + 5 * 64) * MB}            # fp32 streams
    tiles = {"conv_decay": kda_mix.conv_tiles(S, C, taps, jnp.float32),
             "gated_norm": kda_mix.norm_tiles(S, C, D, jnp.float32)}
    assert [s["what"] for s in on_tpu] == ["conv_decay", "gated_norm"]
    for site in on_tpu:
        t = tiles[site["what"]]
        assert site == dict(
            what=site["what"], moved_bytes=moved[site["what"]],
            engine="pallas", rows=t.rows, channels=t.channels, halo=t.halo,
            fwd_vmem_bytes=t.fwd_vmem_bytes, bwd_vmem_bytes=t.bwd_vmem_bytes)
        assert t.bwd_vmem_bytes <= engine.PLAN_VMEM_BUDGET
    assert on_cpu == [dict(
        what=site["what"], moved_bytes=site["moved_bytes"], engine="xla",
        rows=0, channels=0, halo=0, fwd_vmem_bytes=0, bwd_vmem_bytes=0)
        for site in on_tpu]


# ---------------------------------------------------------------------------
# the spans
# ---------------------------------------------------------------------------
def test_the_spans_say_what_each_site_was_given():
    """One kda.lower a KDA layer, one mla.lower (rope none) for the MLA
    layer, one moe.lower and router.lower an expert layer (three layers:
    KDA + dense, MLA + experts, KDA + experts), at the router's published
    counts."""
    names = ("kda.lower", "mla.lower", "moe.lower", "router.lower",
             "kda.mix.lower")
    fluid.flags._VALUES["FLAGS_observability"] = True
    try:
        fluid.reset_default_env()
        spec = models.hybrid_linear_decoder(models.HybridLinearDecoderConfig(
            **{**TINY, **SHORT, "max_length": 128, "n_routed_experts": 256,
               "experts_held": 8, "expert_offset": 0, "top_k": 8}))
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
    assert len(spans["kda.lower"]) == 2
    for site in spans["kda.lower"]:
        assert (site["heads"], site["head_dim"], site["sq"], site["chunk"],
                site["chunks"], site["engine"], site["kept"]) == (
            2, 16, 128, 64, 2, "xla", "out,states")
        assert site["state_bytes"] == 4 * 2 * 2 * 16 * 16
    # heads of 16 are no 128-lane vectors, and this is the CPU
    assert [(s["what"], s["engine"], s["rows"])
            for s in spans["kda.mix.lower"]] == 2 * [
        ("conv_decay", "xla", 0), ("gated_norm", "xla", 0)]
    assert [s["rope"] for s in spans["mla.lower"]] == ["none"]
    assert len(spans["moe.lower"]) == len(spans["router.lower"]) == 2
    for site in spans["moe.lower"]:
        assert (site["experts_total"], site["experts_held"],
                site["top_k"]) == (256, 8, 8)
