"""models/expert_decoder.py, ops/moe_ops.py and the latent_attention op
against the plain reference of moonlight-16b-a3b
(benchmark/configs/moonlight-16b-a3b.reference.py), on the CPU at the
rehearsal's size: loss and every gradient; the shares of one layer add up
to the uncut layer; no token is dropped; the selection bias moves by gamma
the right way and never enters the weights; attention with a value width of
its own against padded v; the flash plans at (2048, 192 | 128); the
`moe.lower` / `mla.lower` spans."""

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
from paddle_tpu import models, observability
from paddle_tpu.kernels import flash_attention as flash_attention_fn
from paddle_tpu.ops import moe_ops

fa = sys.modules["paddle_tpu.kernels.flash_attention"]

TINY = dict(vocab_size=64, max_length=16, n_layer=3, d_model=32, d_inner=64,
            n_head=2, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            kv_lora_rank=24, n_routed_experts=16, experts_held=4,
            expert_offset=4, top_k=3, d_expert=24)
RTOL, ATOL = 2e-4, 2e-5


def _reference():
    return manifest.load_py(os.path.join(
        REPO, "benchmark", "configs", "moonlight-16b-a3b.reference.py"))


def _ref_cfg(cfg: models.ExpertDecoderConfig) -> dict:
    return {
        "num_hidden_layers": cfg.n_layer,
        "first_k_dense_replace": cfg.first_k_dense,
        "num_attention_heads": cfg.n_head, "hidden_size": cfg.d_model,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "kv_lora_rank": cfg.kv_lora_rank,
        "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.rms_norm_eps,
        "n_routed_experts": cfg.experts_held,
        "router_experts": cfg.n_routed_experts,
        "expert_offset": cfg.expert_offset,
        "num_experts_per_tok": cfg.top_k,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "norm_topk_prob": cfg.norm_topk_prob}


def _build(values=None, rows=3, **over):
    """(spec, params, batch, gradients, loss) of one forward-backward pass of
    a tiny model through the Executor; norm scales and selection biases moved
    off their starts so that one that is not applied shows."""
    fluid.reset_default_env()
    cfg = models.ExpertDecoderConfig(**{**TINY, **over})
    spec = models.expert_decoder(cfg)
    pairs = fluid.append_backward(spec.loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    scope = fluid.global_scope()
    rng = np.random.RandomState(11)
    for p in fluid.default_main_program().all_parameters():
        v = np.asarray(scope.find_var(p.name))
        if p.name.endswith("_scale"):
            scope.set_var(p.name, (v + 0.3 * rng.randn(*v.shape)).astype(
                np.float32))
        elif p.name.endswith("_router_bias"):
            scope.set_var(p.name, rng.uniform(-0.2, 0.2, v.shape).astype(
                np.float32))
    for name, v in (values or {}).items():
        scope.set_var(name, v)
    params = {p.name: np.asarray(scope.find_var(p.name))
              for p in fluid.default_main_program().all_parameters()}
    batch = spec.synthetic_batch(rows, seed=5)
    got = exe.run(feed=batch, fetch_list=[spec.loss] + [g for _, g in pairs])
    grads = {p.name: np.asarray(g) for (p, _), g in zip(pairs, got[1:])}
    return spec, params, batch, grads, float(np.ravel(got[0])[0])


_built = once_a_program(_build)


def _reference_loss_and_grad(spec, params, batch, trainable, micro=1):
    loss, grad = as_one_compile(
        _reference().loss_and_grad, params, batch,
        _ref_cfg(spec.extras["config"]), tuple(spec.feed_names),
        frozenset(trainable), micro)
    return float(loss), {k: np.asarray(v) for k, v in grad.items()}


@pytest.mark.parametrize("over,micro", [
    ({}, 1), ({}, 3), ({"use_recompute": False}, 1),
    ({"expert_offset": 0, "experts_held": 8}, 1),
    ({"first_k_dense": 2}, 1), ({"norm_topk_prob": False}, 1)])
def test_program_against_the_plain_reference(over, micro):
    """Loss and every gradient, the selection bias off zero; `micro` parts
    of the batch give the reference the same answer as the whole."""
    spec, params, batch, grads, loss = _built(**over)
    assert not any(n.endswith("_router_bias") for n in grads)
    ref_loss, ref_grads = _reference_loss_and_grad(spec, params, batch,
                                                   grads, micro)
    assert loss == pytest.approx(ref_loss, rel=RTOL)
    assert set(grads) == set(ref_grads)
    for name in sorted(ref_grads):
        scale = max(np.abs(ref_grads[name]).max(), 1.0)
        np.testing.assert_allclose(grads[name], ref_grads[name], rtol=RTOL,
                                   atol=ATOL * scale, err_msg=name)


def _layer_parameters(rng, d, f, experts, shared):
    p = {"l1_router_w": rng.randn(d, experts) * 0.5,
         "l1_router_bias": rng.uniform(-0.2, 0.2, experts),
         "l1_experts_gate_w": rng.randn(experts, d, f) * 0.2,
         "l1_experts_up_w": rng.randn(experts, d, f) * 0.2,
         "l1_experts_down_w": rng.randn(experts, f, d) * 0.2,
         "l1_shared_gate_w": rng.randn(d, shared * f) * 0.2,
         "l1_shared_up_w": rng.randn(d, shared * f) * 0.2,
         "l1_shared_down_w": rng.randn(shared * f, d) * 0.2}
    return {k: jnp.asarray(v, jnp.float32) for k, v in p.items()}


def test_the_shares_add_up_to_the_uncut_layer():
    """Eight shares of one expert layer, each as the program computes it
    (moe_ops.route + held_experts_part on its 2 of 16 experts), with the
    shared expert counted once, are the uncut reference's whole layer; and
    each share alone is the reference given the same share."""
    ref = _reference()
    rng = np.random.RandomState(3)
    d, f, experts, held, k = 32, 24, 16, 2, 3
    p = _layer_parameters(rng, d, f, experts, shared=2)
    x = jnp.asarray(rng.randn(5, 8, d), jnp.float32)
    cfg = {"num_experts_per_tok": k, "norm_topk_prob": True,
           "routed_scaling_factor": 2.446, "router_experts": experts}
    whole = ref._expert_block(p, x, "l1", {**cfg, "n_routed_experts": experts,
                                           "expert_offset": 0})
    idx, weight, _ = moe_ops.route(x.reshape(-1, d), p["l1_router_w"],
                                   p["l1_router_bias"], k, 2.446, True)
    total = ref._mlp(p, x, "l1_shared")
    for share in range(experts // held):
        mine = slice(share * held, (share + 1) * held)
        part = moe_ops.held_experts_part(
            x.reshape(-1, d), idx, weight, p["l1_experts_gate_w"][mine],
            p["l1_experts_up_w"][mine], p["l1_experts_down_w"][mine],
            share * held, experts).reshape(x.shape)
        total = total + part
        cut = {n: v[mine] if "_experts_" in n else v for n, v in p.items()}
        alone = ref._expert_block(cut, x, "l1", {
            **cfg, "n_routed_experts": held, "expert_offset": share * held})
        np.testing.assert_allclose(part + ref._mlp(p, x, "l1_shared"), alone,
                                   rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(total, whole, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("rows", [None, "worst"])
def test_no_token_is_dropped_when_every_token_goes_to_one_held_expert(rows):
    """A router forced to send every token to held expert 1 (and to two
    experts held elsewhere): the group of expert 1 is all T tokens, more
    than the usual buffer's share of it, and every token's term is there."""
    rng = np.random.RandomState(7)
    T, d, f, held, experts, k = 64, 16, 24, 4, 16, 3
    x = jnp.asarray(rng.randn(T, d), jnp.float32)
    gate_w, up_w = (jnp.asarray(rng.randn(held, d, f) * 0.3, jnp.float32)
                    for _ in range(2))
    down_w = jnp.asarray(rng.randn(held, f, d) * 0.3, jnp.float32)
    idx = jnp.asarray(np.tile([9, 5, 12], (T, 1)), jnp.int32)    # 5 = 4 + 1
    weight = jnp.asarray(rng.uniform(0.2, 0.6, (T, k)), jnp.float32)
    usual, worst = moe_ops.row_buffers(T, k, held, experts)
    assert usual == 96 and worst == T * k and T <= usual
    # twice the expected rows, doubled up to the worst case, which is last
    assert moe_ops.row_buffers(8192, 6, 8, 64) == (12288, 24576, 49152)
    assert moe_ops.row_buffers(64, 3, 16, 16) == (192,)
    got = moe_ops.held_experts_part(
        x, idx, weight, gate_w, up_w, down_w, 4, experts,
        rows=worst if rows else None)
    want = weight[:, 1:2] * ((jax.nn.silu(x @ gate_w[1]) * (x @ up_w[1]))
                             @ down_w[1])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    assert float(jnp.min(jnp.sum(jnp.abs(got), axis=-1))) > 0   # every token


@pytest.mark.parametrize("experts,buffers,rows,picked", [
    (16, (96, 192), 192, 1), (32, (48, 96, 192), 96, 1),
    (32, (48, 96, 192), 192, 2)])
def test_more_rows_than_the_usual_buffer_take_the_next_that_holds_them(
        experts, buffers, rows, picked):
    """`rows` assignments held here, more than the usual buffer's: the
    count picks the smallest buffer that holds them (the middle one, or
    the worst case's where every token's three experts are held) and
    nothing is lost, forward or backward."""
    rng = np.random.RandomState(8)
    T, d, f, held, k = 64, 16, 24, 4, 3
    assert moe_ops.row_buffers(T, k, held, experts) == buffers
    assert sum(rows > b for b in buffers[:-1]) == picked
    x = jnp.asarray(rng.randn(T, d), jnp.float32)
    gate_w, up_w = (jnp.asarray(rng.randn(held, d, f) * 0.3, jnp.float32)
                    for _ in range(2))
    down_w = jnp.asarray(rng.randn(held, f, d) * 0.3, jnp.float32)
    # the first rows / k tokens choose three held experts, the rest none
    idx = np.stack([rng.permutation(held)[:k] for _ in range(T)])
    idx[rows // k:] += held
    idx = jnp.asarray(idx, jnp.int32)
    weight = jnp.asarray(rng.uniform(0.2, 0.6, (T, k)), jnp.float32)

    def layer(x, gate_w):
        return jnp.sum(jnp.sin(moe_ops.held_experts_part(
            x, idx, weight, gate_w, up_w, down_w, 0, experts)))

    def plain(x, gate_w):
        y = 0
        for e in range(held):
            g = jnp.sum(jnp.where(idx == e, weight, 0.0), -1)[:, None]
            y = y + g * ((jax.nn.silu(x @ gate_w[e]) * (x @ up_w[e]))
                         @ down_w[e])
        return jnp.sum(jnp.sin(y))

    got = jax.jit(jax.value_and_grad(layer, argnums=(0, 1)))(x, gate_w)
    want = jax.value_and_grad(plain, argnums=(0, 1))(x, gate_w)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_the_pallas_grouped_matmul_equals_xlas_in_the_interpreter():
    """The TPU's engine (megablox gmm and, in its backward, gmm transposed
    and tgmm, at _gmm_tiling's tiles) through the Pallas interpreter
    against jax.lax.ragged_dot, forward and every gradient, with rows past
    the groups' total in the buffer."""
    rng = np.random.RandomState(9)
    T, d, f, held, experts, k = 64, 16, 24, 4, 16, 3
    x = jnp.asarray(rng.randn(T, d), jnp.float32)
    w = jnp.asarray(rng.randn(d, experts) * 0.5, jnp.float32)
    weights = [jnp.asarray(rng.randn(*s) * 0.3, jnp.float32)
               for s in ((held, d, f), (held, d, f), (held, f, d))]
    idx, weight, _ = moe_ops.route(x, w, jnp.zeros((experts,)), k, 2.0, True)
    assert moe_ops._gmm_tiling(96, d, f) == (32, d, f)
    assert moe_ops._gmm_tiling(12288, 2048, 1408) == (512, 512, 1408)
    assert moe_ops._gmm_tiling(12288, 1408, 2048) == (512, 1408, 512)

    def layer(engine):
        def fn(x, weight, gate_w, up_w, down_w):
            return jnp.sum(jnp.sin(moe_ops.held_experts_part(
                x, idx, weight, gate_w, up_w, down_w, 4, experts,
                engine=engine)))
        return jax.jit(jax.value_and_grad(fn, argnums=(0, 1, 2, 3, 4)))(
            x, weight, *weights)

    got, want = layer("interpret"), layer("ragged_dot")
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_the_bias_moves_by_gamma_the_right_way_and_never_enters_the_weights():
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(40, 16), jnp.float32)
    w = jnp.asarray(rng.randn(16, 8), jnp.float32)
    none = jnp.zeros((8,), jnp.float32)
    idx0, weight0, load0 = moe_ops.route(x, w, none, 2, 1.5, True)
    # a bias that lifts expert 3 above every score changes who is chosen ...
    lifted = none.at[3].set(10.0)
    idx1, weight1, load1 = moe_ops.route(x, w, lifted, 2, 1.5, True)
    assert float(load1[3]) == 40 and float(load0[3]) < 40
    assert float(jnp.sum(load0)) == float(jnp.sum(load1)) == 80
    # ... and the weights are still the scores without it, normalised
    scores = jax.nn.sigmoid(x @ w)
    picked = jnp.take_along_axis(scores, idx1, axis=-1)
    np.testing.assert_allclose(
        weight1, 1.5 * picked / picked.sum(-1, keepdims=True), rtol=1e-5)
    assert float(jnp.max(weight1)) <= 1.5
    # the update: gamma towards the mean load, by sign
    from paddle_tpu.core.registry import OpRegistry
    out = OpRegistry.get("moe_bias_update").lower(
        None, {"Bias": [lifted], "Load": [load1[None, :]]},
        {"gamma": 0.001})["BiasOut"][0]
    want = np.asarray(lifted) + 0.001 * np.sign(10.0 - np.asarray(load1))
    np.testing.assert_allclose(out, want, rtol=0, atol=1e-7)
    assert float(out[3]) == pytest.approx(10.0 - 0.001)


def test_a_step_trains_and_the_bias_follows_the_load_outside_the_gradient():
    fluid.reset_default_env()
    cfg = models.ExpertDecoderConfig(**TINY)
    spec = models.expert_decoder(cfg)
    fluid.optimizer.AdamOptimizer(learning_rate=3e-3).minimize(spec.loss)
    main = fluid.default_main_program()
    biases = [p for p in main.all_parameters()
              if p.name.endswith("_router_bias")]
    assert len(biases) == cfg.n_layer - cfg.first_k_dense
    assert not any(p.trainable for p in biases)
    names = {v for op in main.global_block().desc.ops
             for v in op.output_arg_names()}
    assert not any(n.startswith(b.name + "@GRAD") or
                   n.startswith(b.name + "_moment") for b in biases
                   for n in names)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    batch = spec.synthetic_batch(4, 1)
    scope = fluid.global_scope()
    losses = []
    for step in range(1, 6):
        fetched = exe.run(feed=batch, fetch_list=[spec.loss] +
                          spec.extras["loads"])
        losses.append(float(np.ravel(fetched[0])[0]))
        if step == 1:
            loads = [np.asarray(v).reshape(-1) for v in fetched[1:]]
            for b, load in zip(biases, loads):
                assert load.sum() == 4 * cfg.max_length * cfg.top_k
                np.testing.assert_allclose(
                    np.asarray(scope.find_var(b.name)),
                    cfg.bias_update_gamma * np.sign(load.mean() - load),
                    atol=1e-8)
    assert losses[-1] < losses[0]
    assert np.abs(np.asarray(scope.find_var(biases[0].name))).max() <= \
        5 * cfg.bias_update_gamma + 1e-8


# attention at a value width of its own --------------------------------------

@pytest.mark.parametrize("S,d,dv,dtype", [(256, 24, 16, jnp.float32),
                                          (384, 192, 128, jnp.bfloat16)])
def test_flash_with_a_value_width_of_its_own_equals_padded_v(S, d, dv, dtype):
    """Forward and backward through the Pallas kernels (interpreter): v at
    its own width against v zero-padded to the key width and the output
    sliced, which is exact, and against the plain formulation."""
    rng = np.random.RandomState(4)
    q, k = (jnp.asarray(rng.randn(1, 2, S, d), dtype) for _ in range(2))
    v = jnp.asarray(rng.randn(1, 2, S, dv), dtype)
    pad = ((0, 0),) * 3 + ((0, d - dv),)

    def native(q, k, v, force="interpret"):
        return jnp.sum(jnp.sin(flash_attention_fn(
            q, k, v, causal=True, force=force).astype(jnp.float32)))

    def padded(q, k, v):
        out = flash_attention_fn(q, k, jnp.pad(v, pad), causal=True,
                                 force="interpret")
        return jnp.sum(jnp.sin(out[..., :dv].astype(jnp.float32)))

    got = jax.value_and_grad(native, argnums=(0, 1, 2))(q, k, v)
    same = jax.value_and_grad(padded, argnums=(0, 1, 2))(q, k, v)
    plain = jax.value_and_grad(
        lambda *a: native(*a, force="jax"), argnums=(0, 1, 2))(q, k, v)
    tol = dict(rtol=3e-2, atol=3e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(got[0], same[0], rtol=1e-5)
    np.testing.assert_allclose(got[0], plain[0], rtol=tol["rtol"])
    for a, b, c in zip(got[1], same[1], plain[1]):
        assert a.shape == b.shape == c.shape
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), **tol)
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(c, np.float32), **tol)


def test_flash_plans_at_the_cells_attention_shape():
    """(2048, q and k 192, v 128), bf16, causal: the forward plan, the
    backward's engine and blocks; at 4096 and 8192 the row's dQ does not
    fit and the backward runs in chunks of queries (PR 38)."""
    args = (2048, 2048, 192, jnp.bfloat16, True)
    assert fa._plan_blocks(*args, True, 128) == (512, 1024)
    plan = fa._bwd_plan(*args, v_dim=128)
    assert plan == dict(sq=2048, sk=2048, head_dim=192, block_q=512,
                        block_k=512, steps=16, steps_skipped=6,
                        engine="pallas", window=0, chunks=1,
                        skipped_causal=6, skipped_window=0, rows_per_step=1,
                        layout="bhsd", form="blocks")
    # v padded to 192 would plan 1024 x 256, as ISSUE 31 read chip-less
    padded = fa._bwd_plan(*args)
    assert (padded["block_q"], padded["block_k"], padded["engine"]) == \
        (1024, 256, "pallas")
    assert fa.bwd_working_set_bytes(512, 512, 192, 4, "bfloat16", 128) < \
        fa.bwd_working_set_bytes(512, 512, 192, 4, "bfloat16")
    # past S 2048 the row's dQ does not fit: since PR 38 the kernel runs
    # in an outer loop over chunks of 2048 queries (it fell to XLA before)
    for S in (4096, 8192):
        longer = fa._bwd_plan(S, S, 192, jnp.bfloat16, True, v_dim=128)
        assert (longer["engine"], longer["chunks"]) == ("pallas", S // 2048)
    # a value width equal to the head's changes no plan an older site had
    for sq, d in ((2048, 128), (256, 64)):
        old = (sq, sq, d, jnp.bfloat16, True)
        assert fa._bwd_plan(*old) == fa._bwd_plan(*old, v_dim=d)
        assert fa._plan_blocks(*old, True) == fa._plan_blocks(*old, True, d)


def _spans_of_a_step(names, **over):
    """The named spans' counts from one training step lowered abstractly
    for the TPU (nothing compiles or runs)."""
    observability.reset()
    fluid.flags._VALUES["FLAGS_observability"] = True
    try:
        fluid.reset_default_env()
        cfg = models.ExpertDecoderConfig(**{**TINY, **over})
        spec = models.expert_decoder(cfg)
        fluid.optimizer.AdamOptimizer(learning_rate=1e-3).minimize(spec.loss)
        fluid.Executor(fluid.CPUPlace()).run(fluid.default_startup_program())
        observability.reset()
        with fluid.flags.tpu_trace_scope(True):
            compiled, feed_vals, state_vals, rng = fluid.Executor(
                fluid.CPUPlace()).capture_program(
                    fluid.default_main_program(),
                    feed=spec.synthetic_batch(2, 0))
            jax.eval_shape(compiled.raw_fn, feed_vals, state_vals, rng)
        return {n: [dict(s.args) for s in
                    observability.default_tracer().spans() if s.name == n]
                for n in names}
    finally:
        fluid.flags._VALUES["FLAGS_observability"] = False
        observability.reset()


def test_moe_lower_and_mla_lower_say_what_a_site_was_given():
    """At the cell's attention shape and its router's counts, at a width cut
    to two heads: one mla.lower a layer and one moe.lower an expert layer
    (a layer is lowered once: its recomputation is jax's), each attention
    site a Pallas forward and a Pallas backward."""
    spans = _spans_of_a_step(
        ("mla.lower", "moe.lower", "flash.plan", "flash.bwd_plan"),
        max_length=2048, n_layer=3, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, kv_lora_rank=512,
        n_routed_experts=64, experts_held=8, expert_offset=0, top_k=6)
    assert spans["mla.lower"] == 3 * [dict(
        heads=2, qk_dim=192, v_dim=128, kv_rank=512, padded_v=0,
        rope="rotary", kept="out,lse", kept_bytes=2 * 2 * 2048 * (128 * 2 + 4),
        layout="bhsd")]
    T = 2 * 2048
    assert spans["moe.lower"] == 2 * [dict(
        experts_total=64, experts_held=8, top_k=6, row_buffer=6 * T,
        row_buffer_usual=6 * T // 4, row_buffers=3, engine="megablox",
        combine="tgmm", feature_rows=7 * 6 * T // 4, dropped=0,
        scoring="sigmoid")]
    # (the forward is traced once for the step and once more by jax.vjp)
    assert spans["flash.plan"] and len(spans["flash.plan"]) % 3 == 0
    assert all((s["head_dim"], s["block_q"], s["block_k"]) ==
               (192, 512, 1024) for s in spans["flash.plan"])
    assert spans["flash.bwd_plan"] == 3 * [dict(fa._bwd_plan(
        2048, 2048, 192, jnp.bfloat16, True, v_dim=128), kv_heads=2)]


@pytest.mark.parametrize("S, heads, kept", [
    (512, 2, "out,lse"), (256, 2, "out,lse"), (256, 1, "")])
def test_mla_lower_says_what_a_site_keeps_through_its_layers_recomputation(
        S, heads, kept):
    """The value's own width in `kept_bytes` (out is [B, H, S, v_dim]); a
    site the shape leaves on the XLA recompute backward keeps nothing (S 256
    at two batch-head rows in all; at four a grid step takes them all and
    the backward is the Pallas kernel's, PR 53); `recurrence.lower` counts
    the values, a layer a one-site body."""
    spans = _spans_of_a_step(
        ("mla.lower", "flash.bwd_plan", "recurrence.lower"), max_length=S,
        n_layer=2, n_head=heads, qk_nope_head_dim=32, qk_rope_head_dim=16,
        v_head_dim=24)
    assert [(s["kept"], s["kept_bytes"]) for s in spans["mla.lower"]] == \
        2 * [(kept, 2 * heads * S * (24 * 2 + 4) if kept else 0)]
    assert {b["engine"] for b in spans["flash.bwd_plan"]} == {
        "pallas" if kept else "xla"}
    assert [r["kept"] for r in spans["recurrence.lower"]] == 2 * [
        2 if kept else 0]


def test_new_ops_keep_bf16_in_bf16_out_with_fp32_inside():
    from paddle_tpu.core.registry import OpRegistry

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 8, 16), jnp.bfloat16)
    w = jnp.asarray(rng.randn(16, 8) * 0.5, jnp.float32)
    routed = OpRegistry.get("moe_router").lower(
        None, {"X": [x], "Weight": [w], "Bias": [jnp.zeros((8,))]},
        {"top_k": 2, "scaling": 2.0})
    assert routed["TopIdx"][0].dtype == jnp.int32
    assert routed["TopWeight"][0].dtype == jnp.float32
    assert routed["TopIdx"][0].shape == (2, 8, 2)
    experts = [jnp.asarray(rng.randn(*s) * 0.2, jnp.float32)
               for s in ((4, 16, 12), (4, 16, 12), (4, 12, 16))]
    out = OpRegistry.get("moe_experts").lower(
        None, {"X": [x], "TopIdx": routed["TopIdx"],
               "TopWeight": routed["TopWeight"], "GateW": [experts[0]],
               "UpW": [experts[1]], "DownW": [experts[2]]},
        {"experts_total": 8, "expert_offset": 2})["Out"][0]
    assert out.dtype == jnp.bfloat16 and out.shape == x.shape
