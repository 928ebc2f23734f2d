"""models/compressed_decoder.py (ZAYA1-8B's language model: attention in a
compressed latent whose queries and keys pass two causal convolutions, a
top-1 expert block behind a router network that carries state from the
layer before, one table for embedding and head) against its plain
reference, benchmark/configs/zaya1-8b.reference.py, at tiny sizes on the
CPU; the sequence-mixing functions and `rotary_embedding(rotary_dim=)`
alone against jax.numpy; the recurrence that carries two values; the shares
against the uncut layer; every mutant tools/zaya_reference_probe.py holds
the chip's first step to, refused."""

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
from decoder_steps import as_one_compile, once_a_program
from benchmark.harness import manifest
from benchmark.harness import reference as harness_reference
from paddle_tpu import layers, models, observability
from paddle_tpu.models import common
from paddle_tpu.ops import attention_ops, moe_ops

# three layers: the router's state crosses two recompute scopes
TINY = dict(vocab_size=64, max_length=48, n_layer=3, d_model=32, n_head=4,
            n_kv_head=2, head_dim=16, rotary_dim=8, rope_theta=100.0,
            n_routed_experts=8, experts_held=4, expert_offset=4,
            d_expert=24, router_dim=16, residual_init_layers=40)
RTOL, ATOL = 2e-4, 2e-5


def _reference():
    return manifest.load_py(os.path.join(
        REPO, "benchmark", "configs", "zaya1-8b.reference.py"))


def _ref_cfg(cfg: models.CompressedDecoderConfig, query_block=16) -> dict:
    return {
        "num_hidden_layers": cfg.n_layer, "hidden_size": cfg.d_model,
        "num_attention_heads": cfg.n_head, "max_length": cfg.max_length,
        "num_key_value_heads": cfg.n_kv_head, "head_dim": cfg.head_dim,
        "cca_time0": cfg.conv_time0, "cca_time1": cfg.conv_time1,
        "rope_parameters": {"hybrid": {
            "partial_rotary_factor": cfg.rotary_dim / cfg.head_dim,
            "rope_theta": cfg.rope_theta, "rope_type": "default"}},
        "rms_norm_eps": cfg.rms_norm_eps, "num_experts": cfg.experts_held,
        "router_experts": cfg.n_routed_experts,
        "expert_offset": cfg.expert_offset,
        "num_experts_per_tok": cfg.top_k,
        "router_hidden_size": cfg.router_dim,
        "tie_word_embeddings": True, "train_router": cfg.train_router,
        "reference": {"query_block": query_block}}


def _opinions(scope, rng):
    """Every parameter that starts where a mistake could not show, moved:
    the norms' scales and tau off 1, gamma off 0, and q, k, o, the router's
    maps and the experts' down with opinions, so that where a query looks,
    through which convolution, norm and rotary, and which expert a token
    takes all show in the gradient."""
    for p in fluid.default_main_program().all_parameters():
        v = np.asarray(scope.find_var(p.name))
        if p.name.endswith(("_scale", "_tau")):
            v = v + 0.3 * rng.randn(*v.shape)
        elif p.name.endswith("_router_gamma"):
            v = 0.7 * rng.randn(*v.shape)
        elif p.name.endswith(("_attn_q_w", "_attn_k_w", "_router_w",
                              "_router_fc1_w", "_router_fc2_w")):
            v = v * 20
        elif p.name.endswith(("_attn_o_w", "_experts_down_w")):
            v = v * 100
        scope.set_var(p.name, v.astype(np.float32))


def _build(rows=2, **over):
    """(spec, params, batch, gradients, loss) of one forward-backward pass
    of a tiny model through the Executor."""
    fluid.reset_default_env()
    cfg = models.CompressedDecoderConfig(**{**TINY, **over})
    spec = models.compressed_decoder(cfg)
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


def _reference_loss_and_grad(spec, params, batch, trainable, ref=None):
    loss, grad = as_one_compile(
        (ref or _reference()).loss_and_grad, params, batch,
        _ref_cfg(spec.extras["config"]), tuple(spec.feed_names),
        frozenset(trainable), 1)
    return float(loss), {k: np.asarray(v) for k, v in grad.items()}


@pytest.mark.parametrize("over", [
    {}, {"use_recompute": False}, {"expert_offset": 0, "experts_held": 8},
    {"conv_time0": 3, "conv_time1": 1}, {"rotary_dim": 16, "n_kv_head": 1},
    {"train_router": False}])
def test_program_against_the_plain_reference(over):
    """Loss and every parameter's gradient, named parameter by named
    parameter: the convolutions' taps, tau, gamma, the router's maps and
    the ONE table among them."""
    spec, params, batch, grads, loss = _built(**over)
    ref_loss, ref_grads = _reference_loss_and_grad(spec, params, batch, grads)
    assert loss == pytest.approx(ref_loss, rel=RTOL)
    assert set(grads) == set(ref_grads)
    trained = spec.extras["config"].train_router
    for part in ("attn_conv_a_w", "attn_conv_a_b", "attn_conv_b_w",
                 "attn_conv_b_b", "attn_tau") + (
            ("router_gamma", "router_down_w", "router_fc1_w", "router_fc2_w",
             "router_w", "router_norm_scale") if trained else ()):
        # layer 0's gamma multiplies r_prev = 0
        for i in range(part == "router_gamma", 3):
            assert np.abs(ref_grads[f"l{i}_{part}"]).max() > 0, (i, part)
    assert "embed" in grads and "head_w" not in params
    assert trained or not any("_router_" in n for n in grads)
    for name in sorted(ref_grads):
        scale = max(np.abs(ref_grads[name]).max(), 1.0)
        np.testing.assert_allclose(grads[name], ref_grads[name], rtol=RTOL,
                                   atol=ATOL * scale, err_msg=name)


# ---------------------------------------------------------------------------
# the mutants of tools/zaya_reference_probe.py
# ---------------------------------------------------------------------------
sys.path.insert(0, os.path.join(REPO, "tools"))
import zaya_reference_probe as probe  # noqa: E402

MUTANT_TOL = {"loss_rtol": 1e-4, "grad_cos_min": 0.9999,
              "grad_norm_rtol": 1e-3, "param_norm_factor": 1.01}


@pytest.fixture(scope="module")
def one_step():
    return _built(expert_offset=0, experts_held=8)


def _refused(step, name):
    spec, params, batch, grads, loss = step
    ref_loss, ref_grads = as_one_compile(
        probe.mutant(name), params, batch, _ref_cfg(spec.extras["config"]),
        feed_names=tuple(spec.feed_names), trainable=frozenset(grads),
        micro=1)
    prods = {k: (float(np.vdot(grads[k], ref_grads[k])),
                 float(np.vdot(grads[k], grads[k])),
                 float(np.vdot(ref_grads[k], ref_grads[k])))
             for k in grads}
    found = harness_reference.judge(loss, float(ref_loss), prods)
    return harness_reference.problems(found, MUTANT_TOL), found


@pytest.mark.parametrize("name", (None,) + probe.MUTANTS)
def test_the_reference_refuses_each_mutant(one_step, name):
    """The program against the reference is inside the rehearsal's
    tolerances, against each mutant outside at least one (fp8 matmuls
    included)."""
    problems, found = _refused(one_step, name)
    assert bool(problems) == (name is not None), (name, found)


def test_the_mutants_are_issue_43s_and_an_unknown_one_is_an_error():
    assert probe.MUTANTS == (
        "taps_swapped", "conv_b_depthwise", "qk_mean_left_out",
        "mean_after_convs", "value_unshifted", "shift_on_head0",
        "norm_without_sqrt_d", "tau_left_out", "whole_head_rotary",
        "kv_head_mod", "carry_left_out", "relu_router", "gate_one",
        "untied_head", "fp8_matmuls")
    with pytest.raises(KeyError):
        probe.mutant("no_such_mutant")


# ---------------------------------------------------------------------------
# the sequence-mixing functions alone
# ---------------------------------------------------------------------------
def _lax_conv(x, w, bias, before, groups):
    """The same convolution by lax.conv_general_dilated, which the op does
    not use: x [B, S, C] with k - 1 rows of `before` put before it, w [k,
    C_in / groups, C_out]."""
    k = w.shape[0]
    first = jnp.broadcast_to(before, (x.shape[0], k - 1, x.shape[2]))
    y = jax.lax.conv_general_dilated(
        jnp.concatenate([first, x], axis=1), w, window_strides=(1,),
        padding="VALID", dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=groups, precision="highest")
    return y + bias


def _groups_first(x, n):
    """[B, S, n D] -> [B, n, S, D]."""
    return jnp.swapaxes(x.reshape(x.shape[:2] + (n, -1)), 1, 2)


@pytest.mark.parametrize("k,groups,rows_before", [
    (2, "channels", "zeros"), (2, "heads", "zeros"), (3, "channels", "given"),
    (2, "heads", "given"), (1, "heads", "zeros"), (3, "heads", "given")])
def test_causal_conv1d_is_the_convolution_forward_and_backward(
        k, groups, rows_before):
    """`groups` = C (one filter a channel) and = heads (across a head's
    channels), any number of taps, zeros or a given row before the first
    position: forward and every gradient against lax's convolution over
    [B, S, C]."""
    rng = np.random.RandomState(k)
    B, S, n, D = 2, 12, 3, 4
    C = n * D
    x = jnp.asarray(rng.randn(B, S, C), jnp.float32)
    bias = jnp.asarray(rng.randn(C), jnp.float32)
    before = jnp.asarray(rng.randn(C), jnp.float32)
    w = jnp.asarray(rng.randn(*((k, C) if groups == "channels"
                                else (k, n, D, D))), jnp.float32)

    def mine(x, w, bias, before):
        y = attention_ops.causal_conv1d(
            _groups_first(x, n),
            w.reshape(k, n, D) if groups == "channels" else w,
            bias.reshape(n, D),
            before=before.reshape(n, D) if rows_before == "given" else None)
        return jnp.swapaxes(y, 1, 2).reshape(B, S, C)

    def theirs(x, w, bias, before):
        if groups == "channels":
            return _lax_conv(x, w[:, None, :], bias,
                             before * (rows_before == "given"), C)
        return _lax_conv(x, jnp.moveaxis(w, 1, 2).reshape(k, D, C), bias,
                         before * (rows_before == "given"), n)

    got = mine(x, w, bias, before)
    assert got.shape == (B, S, C)
    np.testing.assert_allclose(got, theirs(x, w, bias, before), rtol=1e-5,
                               atol=1e-5)
    weight = jnp.asarray(rng.randn(*got.shape), jnp.float32)
    wrt = (0, 1, 2, 3) if rows_before == "given" and k > 1 else (0, 1, 2)
    for a, b in zip(
            jax.grad(lambda *t: jnp.sum(mine(*t) * weight), wrt)(
                x, w, bias, before),
            jax.grad(lambda *t: jnp.sum(theirs(*t) * weight), wrt)(
                x, w, bias, before)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_the_convolutions_and_the_shift_are_causal():
    """Output t does not move when input t + 1 does; the last tap reads the
    position itself; the shift hands row t - 1 on and zeros before the
    first."""
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(1, 10, 6), jnp.float32)
    xg = _groups_first(x, 2)                               # [1, 2, 10, 3]
    w_own = jnp.asarray(rng.randn(2, 2, 3), jnp.float32)
    w_head = jnp.asarray(rng.randn(2, 2, 3, 3), jnp.float32)
    moved = xg.at[:, :, 6].add(1.0)
    for w in (w_own, w_head):
        a = attention_ops.causal_conv1d(xg, w)
        b = attention_ops.causal_conv1d(moved, w)
        np.testing.assert_array_equal(a[:, :, :6], b[:, :, :6])
        assert np.abs(np.asarray(a - b)[:, :, 6]).max() > 0
    np.testing.assert_allclose(
        attention_ops.causal_conv1d(xg, w_own)[:, :, 3],
        w_own[0] * xg[:, :, 2] + w_own[1] * xg[:, :, 3], rtol=1e-6)
    shifted = attention_ops.causal_shift(x)
    np.testing.assert_array_equal(shifted[:, 1:], x[:, :-1])
    np.testing.assert_array_equal(shifted[:, 0], jnp.zeros_like(x[:, 0]))
    np.testing.assert_array_equal(attention_ops.causal_shift(x, 3)[:, 3:],
                                  x[:, :-3])
    filled = attention_ops.causal_shift(xg, 2, before=xg[:, :, :1], axis=2)
    np.testing.assert_array_equal(filled[:, :, 2:], xg[:, :, :-2])
    np.testing.assert_array_equal(filled[:, :, 1], xg[:, :, 0])


def _mix(q, k, v, p, H, G, rotary_dim, base):
    """compressed_conv_qkv through a Program."""
    fluid.reset_default_env()
    names = ("q", "k", "v", "a_w", "a_b", "b_w", "b_b", "tau")
    values = dict(zip(names, (q, k, v) + tuple(p)))
    ins = {n: layers.data(n, list(np.shape(a)), append_batch_size=False,
                          dtype="float32") for n, a in values.items()}
    outs = layers.compressed_conv_qkv(
        *(ins[n] for n in names), heads=H, kv_heads=G,
        rotary_dim=rotary_dim, rope_base=base)
    exe = fluid.Executor(fluid.CPUPlace())
    return exe.run(feed={n: np.asarray(a) for n, a in values.items()},
                   fetch_list=list(outs))


def test_the_padding_rule_b_sees_as_output_on_zeros_before_position_0():
    """z is padded ONCE: the row convolution B reads before position 0 is
    A's output on zeros, its bias, not zero.  Held to the taps written out
    at position 0, and against the reference's whole latent."""
    ref, rng = _reference(), np.random.RandomState(4)
    B, S, H, G, D = 1, 9, 4, 2, 4
    C, lq = (H + G) * D, H * D
    q = rng.randn(B, S, lq).astype(np.float32)
    k, v = (rng.randn(B, S, G * D).astype(np.float32) for _ in range(2))
    p = (rng.randn(2, C), rng.randn(C), rng.randn(2, H + G, D, D),
         rng.randn(C), 1 + 0.3 * rng.randn(G))
    p = tuple(a.astype(np.float32) for a in p)
    a_w, a_b, b_w, b_b, tau = p
    z = np.concatenate([q, k], -1)[0]
    first = a_w[1] * z[0] + a_b          # A at position 0 (z[-1] = 0)
    before = a_b                         # A at position -1: on zeros
    conv0 = (np.einsum("gi,gio->go", before.reshape(-1, D), b_w[0])
             + np.einsum("gi,gio->go", first.reshape(-1, D), b_w[1])
             ).reshape(-1) + b_b
    n = H + G
    got = attention_ops.causal_conv1d(attention_ops.causal_conv1d(
        _groups_first(jnp.asarray(z)[None], n), a_w.reshape(2, n, D),
        a_b.reshape(n, D)), b_w, b_b.reshape(n, D), before=a_b.reshape(n, D))
    assert got.shape == (1, n, S, D)
    np.testing.assert_allclose(got[0, :, 0].reshape(-1), conv0, rtol=1e-4,
                               atol=1e-5)
    # the whole op against the reference's latent
    cfg = {"num_attention_heads": H, "num_key_value_heads": G,
           "cca_time0": 2, "cca_time1": 2, "rope_parameters": {"hybrid": {
               "partial_rotary_factor": 0.5, "rope_theta": 100.0}}}
    eye = {"x_q_w": jnp.eye(lq + 2 * G * D)[:, :lq],
           "x_k_w": jnp.eye(lq + 2 * G * D)[:, lq:lq + G * D],
           "x_v_w": jnp.eye(lq + 2 * G * D)[:, lq + G * D:],
           "x_conv_a_w": a_w, "x_conv_a_b": a_b, "x_conv_b_w": b_w,
           "x_conv_b_b": b_b, "x_tau": tau}
    want = ref._latent(eye, jnp.concatenate([q, k, v], -1)[0], "x", cfg)
    qo, ko, vo = _mix(q, k, v, p, H, G, D // 2, 100.0)
    np.testing.assert_allclose(qo[0], want[0], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.repeat(ko[0], H // G, 0), want[1],
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.repeat(vo[0], H // G, 0), want[2],
                               rtol=1e-4, atol=1e-5)


def test_rotary_dim_d_is_the_rotary_it_was_and_less_turns_a_prefix():
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(2, 3, 10, 16), jnp.float32)
    whole = attention_ops._rotate(x, 100.0)
    for same in (None, 0, 16):
        np.testing.assert_array_equal(
            attention_ops._rotate(x, 100.0, rotary_dim=same), whole)
    part = attention_ops._rotate(x, 100.0, rotary_dim=8)
    np.testing.assert_array_equal(part[..., 8:], x[..., 8:])
    np.testing.assert_array_equal(part[..., :8],
                                  attention_ops._rotate(x[..., :8], 100.0))
    # through the layer: no attribute where none is asked for, and the
    # same bits
    fluid.reset_default_env()
    t = layers.data("t", [2, 3, 10, 16], append_batch_size=False)
    outs = [layers.rotary_embedding(t, base=100.0),
            layers.rotary_embedding(t, base=100.0, rotary_dim=16),
            layers.rotary_embedding(t, base=100.0, rotary_dim=8)]
    ops = [op for op in fluid.default_main_program().global_block().desc.ops
           if op.type == "rotary_embedding"]
    assert "rotary_dim" not in ops[0].attrs
    got = fluid.Executor(fluid.CPUPlace()).run(
        feed={"t": np.asarray(x)}, fetch_list=outs)
    np.testing.assert_array_equal(got[1], got[0])     # bit for bit
    np.testing.assert_allclose(got[0], whole, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got[2], part, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# the recurrence that carries two values
# ---------------------------------------------------------------------------
def _one_trip_layer_as_it_was(h, body, use_recompute=True):
    """models/common.py::one_trip_layer before it carried a tuple."""
    import contextlib

    from paddle_tpu.core.framework import recompute_scope

    scope = recompute_scope if use_recompute else contextlib.nullcontext
    with scope():
        rec = layers.Recurrence(trips=1)
        with rec.block():
            carried = rec.carry(h)
            out, handed_out = body(carried)
            rec.update(carried, out)
            for value in handed_out:
                rec.output(value)
        h = rec.final(carried)
    return h, rec


def _program_text(one_trip_layer, monkeypatch):
    import importlib

    wd = importlib.import_module("paddle_tpu.models.windowed_decoder")
    monkeypatch.setattr(wd, "one_trip_layer", one_trip_layer)
    fluid.reset_default_env()
    spec = models.windowed_decoder(models.WindowedDecoderConfig(
        vocab_size=64, max_length=48, d_model=32, n_head=4, n_kv_head=2,
        head_dim=16, sliding_window=12, n_routed_experts=16, experts_held=4,
        d_expert=24))
    fluid.optimizer.AdamOptimizer(learning_rate=1e-3).minimize(spec.loss)
    program = fluid.default_main_program()
    return [[(op.type, sorted(op.inputs.items()), sorted(op.outputs.items()),
              sorted((k, str(v)) for k, v in op.attrs.items()
                     if k != "__op_uid__" and "uid" not in k))
             for op in program.block(i).desc.ops]
            for i in range(program.num_blocks())]


def test_one_value_builds_the_program_it_built(monkeypatch):
    """The three builders that hand one value on get, op for op and name
    for name, the program they got."""
    was = _program_text(_one_trip_layer_as_it_was, monkeypatch)
    now = _program_text(common.one_trip_layer, monkeypatch)
    assert now == was and sum(len(b) for b in now) > 100


def test_two_values_are_two_carries_of_one_recurrence():
    fluid.reset_default_env()
    spec = models.compressed_decoder(models.CompressedDecoderConfig(**TINY))
    block = fluid.default_main_program().global_block()
    recs = [op for op in block.desc.ops if op.type == "recurrence"]
    assert len(recs) == 3
    for op in recs:
        assert len(op.input("Init")) == 2 and len(op.output("Final")) == 2
        assert op.attrs["trips"] == 1 and op.attrs.get("@recompute@")
    # layer l + 1 starts from what layer l handed on, state included
    for a, b in zip(recs, recs[1:]):
        assert b.input("Init") == a.output("Final")
    assert spec.extras["router_state"].name == recs[-1].output("Final")[1]
    assert tuple(spec.extras["router_state"].shape[1:]) == (48, 16)


# ---------------------------------------------------------------------------
# the router
# ---------------------------------------------------------------------------
def test_the_router_network_and_its_gradients():
    """moe_router on x of the router's width, top 1, unnormalised, behind
    the network: the chosen expert and its gate against the reference's
    softmax and argmax, and the gradient of a function of the gate and of
    the state handed on, to the five maps, gamma, the norm's scale, the
    input and the state handed IN."""
    ref, rng = _reference(), np.random.RandomState(8)
    d, R, E, T = 32, 16, 8, 40
    fluid.reset_default_env()
    cfg = models.CompressedDecoderConfig(**{**TINY, "d_model": d})
    from paddle_tpu.models.compressed_decoder import _CompressedBuilder

    x = layers.data("x", [1, T, d], append_batch_size=False)
    r_prev = layers.data("r_prev", [1, T, R], append_batch_size=False)
    x.stop_gradient = r_prev.stop_gradient = False
    idx, weight, r = _CompressedBuilder(cfg).router(x, r_prev, "l0")
    cw = layers.data("cw", [1, T, 1], append_batch_size=False)
    cr = layers.data("cr", [1, T, R], append_batch_size=False)
    loss = layers.elementwise_add(
        layers.reduce_sum(layers.elementwise_mul(weight, cw)),
        layers.reduce_sum(layers.elementwise_mul(r, cr)))
    pairs = fluid.append_backward(loss)
    wrt = [g for _, g in pairs] + [
        fluid.default_main_program().global_block().var(n + "@GRAD")
        for n in ("x", "r_prev")]
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    scope = fluid.global_scope()
    _opinions(scope, rng)
    params = {p.name: jnp.asarray(np.asarray(scope.find_var(p.name)))
              for p in fluid.default_main_program().all_parameters()}
    feed = {"x": rng.randn(1, T, d), "r_prev": rng.randn(1, T, R),
            "cw": rng.randn(1, T, 1), "cr": rng.randn(1, T, R)}
    feed = {n: v.astype(np.float32) for n, v in feed.items()}
    got = exe.run(feed=feed, fetch_list=[idx, weight, r] + wrt)

    ref_cfg = {"rms_norm_eps": cfg.rms_norm_eps, "num_experts_per_tok": 1}

    def f(params, x, r_prev):
        g, r = ref._router(params, x, r_prev, "l0", ref_cfg)
        return (jnp.sum(jnp.sum(g, axis=-1) * feed["cw"][0, :, 0])
                + jnp.sum(r * feed["cr"][0])), (g, r)

    with jax.default_matmul_precision("highest"):
        (_, (g, r_ref)), grads = jax.value_and_grad(
            f, argnums=(0, 1, 2), has_aux=True)(
                params, jnp.asarray(feed["x"][0]),
                jnp.asarray(feed["r_prev"][0]))
    np.testing.assert_array_equal(got[0][0, :, 0], np.argmax(g, axis=-1))
    assert len(set(got[0].ravel().tolist())) > 2      # a router with opinions
    np.testing.assert_allclose(got[1][0, :, 0], np.max(g, axis=-1), rtol=1e-5)
    assert np.all(got[1] < 1.0) and np.all(np.sum(g > 0, axis=-1) == 1)
    np.testing.assert_allclose(got[2][0], r_ref, rtol=1e-5, atol=1e-6)
    names = [p.name for p, _ in pairs]
    assert {n.replace("l0_router_", "") for n in names} == {
        "down_w", "down_b", "gamma", "norm_scale", "fc1_w", "fc1_b", "fc2_w",
        "fc2_b", "w"}
    for name, mine in zip(names, got[3:]):
        assert np.abs(grads[0][name]).max() > 0, name
        np.testing.assert_allclose(mine, grads[0][name], rtol=2e-4,
                                   atol=1e-6, err_msg=name)
    np.testing.assert_allclose(got[-2][0], grads[1], rtol=2e-4, atol=1e-6)
    np.testing.assert_allclose(got[-1][0], grads[2], rtol=2e-4, atol=1e-6)
    assert np.abs(grads[2]).max() > 0                  # through gamma


def test_the_two_shares_add_up_to_the_uncut_layer():
    """Two shares of one expert layer, each as the program computes it
    (moe_ops.route under the softmax rule at top 1, unnormalised, on the
    router network's last hidden value + held_experts_part on experts 0-7
    and 8-15 of 16), add up to the uncut reference's whole 16-expert
    block; each share alone is the reference given the same share, and
    both hand on the same state."""
    ref, rng = _reference(), np.random.RandomState(3)
    d, f, R, experts, held, T = 32, 24, 16, 16, 8, 40
    n = "l1_router_"
    p = {n + "down_w": rng.randn(d, R) * 0.3, n + "down_b": rng.randn(R),
         n + "gamma": rng.randn(R), n + "norm_scale": 1 + 0.3 * rng.randn(R),
         n + "fc1_w": rng.randn(R, R) * 0.5, n + "fc1_b": rng.randn(R) * 0.1,
         n + "fc2_w": rng.randn(R, R) * 0.5, n + "fc2_b": rng.randn(R) * 0.1,
         "l1_router_w": rng.randn(R, experts),
         "l1_experts_gate_w": rng.randn(experts, d, f) * 0.2,
         "l1_experts_up_w": rng.randn(experts, d, f) * 0.2,
         "l1_experts_down_w": rng.randn(experts, f, d) * 0.2}
    p = {name: jnp.asarray(v, jnp.float32) for name, v in p.items()}
    x = jnp.asarray(rng.randn(T, d), jnp.float32)
    r_prev = jnp.asarray(rng.randn(T, R), jnp.float32)
    cfg = {"num_experts_per_tok": 1, "router_experts": experts,
           "rms_norm_eps": 1e-5}
    with jax.default_matmul_precision("highest"):
        uncut, r_uncut = ref._expert_block(p, x, r_prev, "l1", {
            **cfg, "num_experts": experts, "expert_offset": 0})
        # the network up to the op's input, as the reference writes it
        r = ref._carry(x @ p[n + "down_w"] + p[n + "down_b"], p[n + "gamma"],
                       r_prev)
        h = ref._rms_norm(r, p[n + "norm_scale"], 1e-5)
        h = ref._act(h @ p[n + "fc1_w"] + p[n + "fc1_b"])
        h = ref._act(h @ p[n + "fc2_w"] + p[n + "fc2_b"])
        idx, weight, load = moe_ops.route(h, p["l1_router_w"], None, 1, 1.0,
                                          False, scoring="softmax")
        assert idx.shape == (T, 1) and float(jnp.sum(load)) == T
        total = 0.0
        for offset in range(0, experts, held):
            mine = slice(offset, offset + held)
            share = moe_ops.held_experts_part(
                x, idx, weight, p["l1_experts_gate_w"][mine],
                p["l1_experts_up_w"][mine], p["l1_experts_down_w"][mine],
                offset, experts)
            want, r_share = ref._expert_block(
                {**p, **{k: p[k][mine] for k in p if "_experts_" in k}}, x,
                r_prev, "l1",
                {**cfg, "num_experts": held, "expert_offset": offset})
            np.testing.assert_allclose(share, want, rtol=1e-4, atol=1e-5)
            np.testing.assert_array_equal(r_share, r_uncut)
            total = total + share
    assert np.abs(np.asarray(total)).max() > 0
    np.testing.assert_allclose(total, uncut, rtol=1e-4, atol=1e-5)
    assert moe_ops.row_buffers(16384, 1, 8, 16) == (16384,)


# ---------------------------------------------------------------------------
# spans, starts
# ---------------------------------------------------------------------------
def _spans_of_a_step(names, **over):
    """The named spans' counts from one training step lowered abstractly
    for the TPU (nothing compiles or runs)."""
    observability.reset()
    fluid.flags._VALUES["FLAGS_observability"] = True
    try:
        fluid.reset_default_env()
        cfg = models.CompressedDecoderConfig(**{**TINY, **over})
        spec = models.compressed_decoder(cfg)
        fluid.optimizer.AdamOptimizer(learning_rate=1e-3).minimize(spec.loss)
        fluid.Executor(fluid.CPUPlace()).run(fluid.default_startup_program())
        observability.reset()
        with fluid.flags.tpu_trace_scope(True):
            compiled, feed_vals, state_vals, rng = fluid.Executor(
                fluid.CPUPlace()).capture_program(
                    fluid.default_main_program(),
                    feed=spec.synthetic_batch(1, 0))
            jax.eval_shape(compiled.raw_fn, feed_vals, state_vals, rng)
        return {n: [dict(s.args) for s in
                    observability.default_tracer().spans() if s.name == n]
                for n in names}
    finally:
        fluid.flags._VALUES["FLAGS_observability"] = False
        observability.reset()


def test_the_spans_say_what_each_site_was_given():
    """S 512: `cca.lower` and `router.lower` a site, the `attn.lower` of
    the flash call inside `cca.attend` at 4 query heads on 2, every
    backward on the Pallas kernel, the expert block's one buffer."""
    S = 512
    spans = _spans_of_a_step(
        ("cca.lower", "router.lower", "attn.lower", "flash.plan",
         "flash.bwd_plan", "moe.lower", "recurrence.lower", "ce.lower"),
        max_length=S, train_router=False)
    pairs = S * (S + 1) // 2
    # a head of 16 does not tile: the jax.numpy engine; the three passes
    # (forward, the recomputed forward, backward) move the bf16 q~, k~, v~
    # and as many bytes out, 2 + 2 + 3 times
    assert spans["cca.lower"] and all(s == dict(
        heads=4, kv_heads=2, latent_q=64, latent_k=32, conv_time0=2,
        conv_time1=2, conv_groups=6, rotary_dim=8, sq=S, pairs=pairs,
        engine="xla", tile=0, moved_bytes=7 * S * (64 + 32 + 32) * 2)
        for s in spans["cca.lower"])
    assert spans["router.lower"] and all(s == dict(
        width=16, experts=8, carried=16, trained=0)
        for s in spans["router.lower"])
    assert all(s == dict(kind="full", window=0, heads=4, kv_heads=2, sq=S,
                         pairs=pairs, rope="partial", kept="out,lse",
                         kept_bytes=4 * S * (16 * 2 + 4), layout="bhsd")
               for s in spans["attn.lower"])
    assert all(p["kv_heads"] == 2 and p["causal"] for p in spans["flash.plan"])
    assert len(spans["flash.bwd_plan"]) == 3
    assert all(b["engine"] == "pallas" and b["kv_heads"] == 2
               for b in spans["flash.bwd_plan"])
    assert all(m["top_k"] == 1 and m["row_buffers"] == 1
               and m["row_buffer"] == S and m["experts_held"] == 4
               and m["experts_total"] == 8 and m["scoring"] == "softmax"
               for m in spans["moe.lower"])
    assert all(r["bodies_lowered"] == 1 and r["trips"] == 1
               for r in spans["recurrence.lower"])
    assert [c["classes"] for c in spans["ce.lower"]] == [64]


@pytest.mark.parametrize("over, engine, tile, passes", [
    (dict(head_dim=128, rotary_dim=64), "pallas", 512, 7),
    (dict(head_dim=128, rotary_dim=64, use_recompute=False), "pallas", 512,
     5),
    (dict(head_dim=128, rotary_dim=64, max_length=320), "xla", 0, 7),
    (dict(head_dim=64, rotary_dim=64), "xla", 0, 7)])
def test_cca_lower_says_which_engine_a_site_was_given(over, engine, tile,
                                                      passes):
    """A head of 128 at an S of whole tiles lowers for the TPU to the
    kernel pair of kernels/cca_mix.py (`engine` pallas, the forward's
    `tile`); an S no tile divides or a head of 64 to the jax.numpy form;
    `moved_bytes` counts the recomputed forward where the layer is
    rematerialised."""
    sizes = {"max_length": 512, **over}
    spans = _spans_of_a_step(("cca.lower",), **sizes)["cca.lower"]
    S, width = sizes["max_length"], (4 + 2 + 2) * sizes["head_dim"]
    assert len(spans) == TINY["n_layer"]
    assert all((s["engine"], s["tile"], s["moved_bytes"]) == (
        engine, tile, passes * S * width * 2) for s in spans), spans


def test_on_a_mesh_of_several_devices_the_site_takes_the_jnp_form():
    """XLA cannot partition a Mosaic kernel: the data-parallel step over
    four (virtual) devices lowers the shape that tiles to `engine` xla,
    where one device's step lowers it to the kernel pair."""
    from paddle_tpu.core.executor import _RunPlan
    from paddle_tpu.parallel import ParallelExecutor, make_mesh

    observability.reset()
    fluid.flags._VALUES["FLAGS_observability"] = True
    try:
        fluid.reset_default_env()
        spec = models.compressed_decoder(models.CompressedDecoderConfig(
            **{**TINY, "max_length": 512, "head_dim": 128, "rotary_dim": 64}))
        fluid.optimizer.AdamOptimizer(learning_rate=1e-3).minimize(spec.loss)
        fluid.Executor(fluid.CPUPlace()).run(fluid.default_startup_program())
        mesh = make_mesh({"dp": 4}, devices=jax.devices()[:4])
        pe = ParallelExecutor(loss_name=spec.loss.name, mesh=mesh)
        batch, prog = spec.synthetic_batch(4, 0), fluid.default_main_program()
        plan = _RunPlan(prog, sorted(batch), [spec.loss.name])
        block0 = prog.desc.block(0)
        observability.reset()
        with fluid.flags.tpu_trace_scope(True), mesh.mesh:
            jax.eval_shape(pe._compile(plan).fn, *(
                tuple(plan.feed_values(batch, block0)),
                tuple(plan.state_values(fluid.global_scope(), block0)),
                plan.rng_value(fluid.global_scope(), prog)))
        spans = [dict(s.args) for s in observability.default_tracer().spans()
                 if s.name == "cca.lower"]
    finally:
        fluid.flags._VALUES["FLAGS_observability"] = False
        observability.reset()
    assert len(spans) == TINY["n_layer"]
    assert all((s["engine"], s["tile"]) == ("xla", 0) for s in spans), spans


@pytest.mark.parametrize("S, kept", [(512, "out,lse"), (256, "")])
def test_the_flash_site_keeps_out_and_lse_through_the_layers_recomputation(
        S, kept):
    """The site inside `cca.attend`: where its backward is the Pallas
    kernel its output and logsumexp survive the recomputation of the layer
    (`attn.lower`'s `kept`, `kept_bytes`), and the layer's
    `recurrence.lower`, which carries two values, counts two kept; at S 256
    the XLA recompute backward keeps nothing."""
    spans = _spans_of_a_step(
        ("attn.lower", "flash.bwd_plan", "recurrence.lower"), max_length=S)
    assert spans["attn.lower"] and all(
        (s["kept"], s["kept_bytes"]) == (
            kept, 4 * S * (16 * 2 + 4) if kept else 0)
        for s in spans["attn.lower"])
    assert {b["engine"] for b in spans["flash.bwd_plan"]} == {
        "pallas" if kept else "xla"}
    assert [(r["recompute"], r["kept"]) for r in spans["recurrence.lower"]] \
        == 3 * [(1, 2 if kept else 0)]


def test_where_the_parameters_start():
    """N(0, 0.02) but the stream's two writers (scaled by the published
    depth), the convolutions U(+-1 / sqrt(fan_in)), tau 1, gamma and the
    router's biases 0; one table and no head."""
    fluid.reset_default_env()
    models.compressed_decoder(models.CompressedDecoderConfig(
        **{**TINY, "d_model": 64, "d_expert": 48, "vocab_size": 256,
           "head_dim": 32, "router_dim": 64, "n_layer": 1}))
    fluid.Executor(fluid.CPUPlace()).run(fluid.default_startup_program())
    scope = fluid.global_scope()
    value = {p.name: np.asarray(scope.find_var(p.name))
             for p in fluid.default_main_program().all_parameters()}
    assert "head_w" not in value and value["embed"].shape == (256, 64)
    scaled = 0.02 / np.sqrt(2 * 40)
    for name, v in value.items():
        if name.endswith(("_attn_o_w", "_experts_down_w")):
            assert np.std(v) == pytest.approx(scaled, rel=0.08), name
        elif "_conv_" in name:
            fan_in = 2 if "_conv_a_" in name else 2 * 32
            assert np.abs(v).max() <= fan_in ** -0.5, name
            assert np.std(v) == pytest.approx(
                fan_in ** -0.5 / np.sqrt(3), rel=0.25), name
        elif name.endswith(("_tau", "_scale")):
            np.testing.assert_array_equal(v, np.ones_like(v))
        elif name.endswith(("_router_gamma", "_b")):
            np.testing.assert_array_equal(v, np.zeros_like(v))
        else:
            assert np.std(v) == pytest.approx(0.02, rel=0.08), name
