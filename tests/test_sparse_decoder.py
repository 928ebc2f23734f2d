"""models/sparse_decoder.py, the sparse_attention op with its kernels
(kernels/sparse_attention.py), the softmax rule of ops/moe_ops.py::route and
rotary positions on three streams, against the plain reference of
keye-vl-2.0-30b-a3b (benchmark/configs/keye-vl-2.0-30b-a3b.reference.py), on
the CPU at small sizes with UNEQUAL position streams: loss, every gradient
and the chosen sets; the shares of one layer add up to the uncut layer;
below topk positions the layer is dense causal grouped-query attention; the
two stop-gradients; the exact selection under ties; the mutants a wrong
implementation would be; the `dsa.lower` / `moe.lower` spans."""

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
from paddle_tpu.kernels import sparse_attention as dsa
from paddle_tpu.ops import attention_ops, moe_ops

TINY = dict(vocab_size=64, max_length=32, n_layer=2, d_model=32, n_head=4,
            n_kv_head=2, head_dim=16, mrope_section=(2, 2, 4), index_heads=3,
            index_dim=8, index_topk=8, q_chunk=8, kv_chunk=8,
            n_routed_experts=16, experts_held=4, expert_offset=4, top_k=3,
            d_expert=24)
RTOL, ATOL = 2e-4, 2e-5


def _reference():
    return manifest.load_py(os.path.join(
        REPO, "benchmark", "configs", "keye-vl-2.0-30b-a3b.reference.py"))


def _ref_cfg(cfg: models.SparseDecoderConfig, query_block=8) -> dict:
    return {
        "num_hidden_layers": cfg.n_layer, "hidden_size": cfg.d_model,
        "num_attention_heads": cfg.n_head,
        "num_key_value_heads": cfg.n_kv_head, "head_dim": cfg.head_dim,
        "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.rms_norm_eps,
        "rope_scaling": {"mrope_section": list(cfg.mrope_section)},
        "sa_config": {"indexer_num_heads": cfg.index_heads,
                      "indexer_head_dim": cfg.index_dim,
                      "topk": cfg.index_topk},
        "num_experts": cfg.experts_held,
        "router_experts": cfg.n_routed_experts,
        "expert_offset": cfg.expert_offset,
        "num_experts_per_tok": cfg.top_k,
        "norm_topk_prob": cfg.norm_topk_prob,
        "reference": {"query_block": query_block}}


def _unequal_positions(rows, streams, S, seed=2):
    """A token's temporal, height and width positions, all different: a
    run of text, then a grid (an image's patches), then text again."""
    rng = np.random.RandomState(seed)
    t = np.cumsum(rng.randint(0, 2, size=(rows, S)), axis=1)
    h = rng.randint(0, 7, size=(rows, S))
    w = np.arange(S)[None, :] % 5 + rng.randint(0, 3, size=(rows, 1))
    return np.stack([t, h, w][:streams], axis=1).astype(np.int32)


def _build(rows=2, loss="loss", **over):
    """(spec, params, batch, gradients, loss) of one forward-backward pass of
    a tiny model through the Executor, the norms' scales and the LayerNorm's
    shift moved off their starts so that one that is not applied shows."""
    fluid.reset_default_env()
    cfg = models.SparseDecoderConfig(**{**TINY, **over})
    spec = models.sparse_decoder(cfg)
    target = spec.loss if loss == "loss" else spec.extras[loss]
    pairs = fluid.append_backward(target)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    scope = fluid.global_scope()
    rng = np.random.RandomState(11)
    for p in fluid.default_main_program().all_parameters():
        v = np.asarray(scope.find_var(p.name))
        if p.name.endswith(("_scale", "_kn_bias")):
            scope.set_var(p.name, (v + 0.3 * rng.randn(*v.shape)).astype(
                np.float32))
        elif "_index_" in p.name or p.name.endswith("_router_w"):
            # an index and a router with opinions
            scope.set_var(p.name, (v * 20).astype(np.float32))
    params = {p.name: np.asarray(scope.find_var(p.name))
              for p in fluid.default_main_program().all_parameters()}
    batch = spec.synthetic_batch(rows, seed=5)
    batch[spec.feed_names[2]] = _unequal_positions(
        rows, len(cfg.mrope_section), cfg.max_length)
    got = exe.run(feed=batch, fetch_list=[target] + [g for _, g in pairs])
    grads = {p.name: np.asarray(g) for (p, _), g in zip(pairs, got[1:])}
    return spec, params, batch, grads, float(np.ravel(got[0])[0])


_built = once_a_program(_build)


def _reference_loss_and_grad(spec, params, batch, trainable, ref=None,
                             **cfg_over):
    cfg = {**_ref_cfg(spec.extras["config"]), **cfg_over}
    loss, grad = as_one_compile(
        (ref or _reference()).loss_and_grad, params, batch, cfg,
        tuple(spec.feed_names), frozenset(trainable), 1)
    return float(loss), {k: np.asarray(v) for k, v in grad.items()}


def _assert_close(grads, ref_grads):
    assert set(grads) == set(ref_grads)
    for name in sorted(ref_grads):
        scale = max(np.abs(ref_grads[name]).max(), 1.0)
        np.testing.assert_allclose(grads[name], ref_grads[name], rtol=RTOL,
                                   atol=ATOL * scale, err_msg=name)


@pytest.mark.parametrize("over", [
    {}, {"use_recompute": False}, {"expert_offset": 0, "experts_held": 16},
    {"index_topk": 3, "q_chunk": 16}, {"norm_topk_prob": False}])
def test_program_against_the_plain_reference(over):
    """Loss (cross entropy + index loss) and every parameter's gradient,
    the index's included, on unequal position streams."""
    spec, params, batch, grads, loss = _built(**over)
    ref_loss, ref_grads = _reference_loss_and_grad(spec, params, batch, grads)
    assert loss == pytest.approx(ref_loss, rel=RTOL)
    _assert_close(grads, ref_grads)


def test_the_index_loss_moves_the_index_alone_and_the_cross_entropy_the_rest():
    """The two stop-gradients: of L_I every gradient outside the index is
    zero, of the cross entropy every gradient of the index."""
    _, _, _, ce, _ = _build(loss="cross_entropy", n_layer=1)
    _, _, _, kl, _ = _build(loss="index_loss", n_layer=1)
    for grads, zero in ((kl, lambda n: "_index_" not in n),
                        (ce, lambda n: "_index_" in n)):
        moved = {n for n, g in grads.items() if np.abs(g).max() > 0}
        assert moved and not {n for n in moved if zero(n)}, moved
    # and together they are the step's gradient: nothing is counted twice
    _, _, _, whole, _ = _build(n_layer=1)
    for name, g in whole.items():
        want = ce.get(name, 0) + kl.get(name, 0)
        np.testing.assert_allclose(g, want, rtol=1e-5, atol=1e-7,
                                   err_msg=name)


def _op_inputs(rng, B, H, G, S, D, Hi, Di):
    def normal(*shape):
        return jnp.asarray(rng.randn(*shape), jnp.float32)

    return (normal(B, H, S, D), normal(B, G, S, D), normal(B, G, S, D),
            normal(B, Hi, S, Di), normal(B, S, Di), normal(B, S, Hi))


@pytest.mark.parametrize("engine", ["jax", "interpret"],
                         ids=["xla", "interpret"])
def test_below_topk_positions_the_layer_is_dense_causal_gqa(engine):
    """While t < topk every causal key is chosen, whatever the index says:
    the output is plain causal grouped-query attention, and both engines
    give it (the Pallas kernels in the interpreter)."""
    B, H, G, S, D = 2, 4, 2, 32, 16
    q, k, v, qi, ki, w = _op_inputs(np.random.RandomState(0), B, H, G, S, D,
                                    3, 8)
    with jax.default_matmul_precision("highest"):
        out, _ = dsa.sparse_attention(q, k, v, qi, ki, w, topk=S,
                                      scale=D ** -0.5, q_chunk=8, kv_chunk=8,
                                      force=engine)
        kr, vr = (jnp.repeat(x, H // G, axis=1) for x in (k, v))
        s = jnp.einsum("bhtd,bhsd->bhts", q, kr) * D ** -0.5
        causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
        want = jnp.einsum("bhts,bhsd->bhtd", jax.nn.softmax(
            jnp.where(causal, s, -1e30), axis=-1), vr)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)


def test_the_kernels_in_the_interpreter_against_plain_jax():
    """Out, the index loss and all six gradients of the Pallas engine
    (forward, probability pass and the one backward kernel, dead blocks
    skipped) against the jax.numpy engine, at more kv blocks than chunks'
    keys so that blocks above the diagonal are dead."""
    args = _op_inputs(np.random.RandomState(1), 1, 4, 2, 64, 16, 3, 8)

    def loss(engine):
        def f(*a):
            out, kl = dsa.sparse_attention(
                *a, topk=8, scale=0.25, q_chunk=16, kv_chunk=8,
                force=engine)
            return jnp.sum(out * jnp.cos(out)) + 3.0 * kl, (out, kl)
        return jax.value_and_grad(f, argnums=tuple(range(6)), has_aux=True)

    with jax.default_matmul_precision("highest"):
        (_, (out, kl)), grads = loss("interpret")(*args)
        (_, (want, want_kl)), want_grads = loss("jax")(*args)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
    assert float(kl) == pytest.approx(float(want_kl), rel=1e-5)
    for g, r in zip(grads, want_grads):
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-5)


def test_the_selection_is_exact_ties_to_the_lower_position():
    """select_topk against lax.top_k (which puts the lower index first
    among equals) on scores full of ties, zeros of both signs, infinities,
    and rows with fewer valid positions than k."""
    rng = np.random.RandomState(4)
    T, S, k = 16, 64, 7
    scores = rng.randint(-2, 3, size=(T, S)).astype(np.float32)
    scores[0] = 0.0
    scores[1, ::2] = -0.0
    scores[2, :5] = np.inf
    scores[3, 10:] = -np.inf
    scores[4:8] = rng.randn(4, S)
    valid = np.arange(S)[None, :] <= (np.arange(T)[:, None] * 5 + 1)
    got, thr = dsa.select_topk(jnp.asarray(scores), jnp.asarray(valid), k)
    again, _ = dsa.select_topk(jnp.asarray(scores), jnp.asarray(valid), k,
                               thr)          # as the backward finds it
    np.testing.assert_array_equal(got, again)
    got = np.asarray(got)
    canon = np.where(scores == 0.0, 0.0, scores)   # as index_scores emits
    _, idx = jax.lax.top_k(jnp.where(valid, canon, -jnp.inf), k)
    want = np.zeros((T, S), bool)
    want[np.arange(T)[:, None], np.asarray(idx)] = True
    want &= valid
    assert (got.sum(1) == np.minimum(valid.sum(1), k)).all()
    np.testing.assert_array_equal(got, want)


def test_the_programs_chosen_sets_are_the_references():
    """The op's chosen keys, a chunk at a time by the bitwise search,
    against the reference's lax.top_k and scatter on the same scores."""
    ref = _reference()
    rng = np.random.RandomState(6)
    S, Hi, Di, topk, tq = 64, 3, 8, 8, 16
    qi = jnp.asarray(rng.randn(Hi, S, Di), jnp.float32)
    ki = jnp.asarray(rng.randn(S, Di), jnp.float32)
    w = jnp.asarray(rng.randn(S, Hi), jnp.float32)
    with jax.default_matmul_precision("highest"):
        for first in range(0, S, tq):
            rows = slice(first, first + tq)
            mask = dsa._chunk_mask(qi[:, rows], ki, w[rows], first, topk)[1]
            want = ref._chosen(ref._index_scores(qi[:, rows], ki, w[rows]),
                               first, topk)
            np.testing.assert_array_equal(np.asarray(mask), np.asarray(want))


def test_the_shares_add_up_to_the_uncut_layer():
    """Eight shares of one expert layer, each as the program computes it
    (moe_ops.route under the softmax rule + held_experts_part on its 2 of
    16 experts), add up to the uncut reference's whole expert block; each
    share alone is the reference given the same share; attention, the
    index and the router are whole on every chip and counted once."""
    ref = _reference()
    rng = np.random.RandomState(3)
    d, f, experts, held, k = 32, 24, 16, 2, 3
    p = {"l1_router_w": rng.randn(d, experts) * 0.5,
         "l1_experts_gate_w": rng.randn(experts, d, f) * 0.2,
         "l1_experts_up_w": rng.randn(experts, d, f) * 0.2,
         "l1_experts_down_w": rng.randn(experts, f, d) * 0.2}
    p = {n: jnp.asarray(v, jnp.float32) for n, v in p.items()}
    x = jnp.asarray(rng.randn(40, d), jnp.float32)
    cfg = {"num_experts_per_tok": k, "norm_topk_prob": True,
           "router_experts": experts}
    with jax.default_matmul_precision("highest"):
        uncut = ref._expert_block(p, x, "l1", {
            **cfg, "num_experts": experts, "expert_offset": 0})
        idx, weight, _ = moe_ops.route(x, p["l1_router_w"], None, k, 1.0,
                                       True, scoring="softmax")
        total = 0.0
        for offset in range(0, experts, held):
            mine = slice(offset, offset + held)
            share = moe_ops.held_experts_part(
                x, idx, weight, p["l1_experts_gate_w"][mine],
                p["l1_experts_up_w"][mine], p["l1_experts_down_w"][mine],
                offset, experts)
            want = ref._expert_block(
                {**p, **{n: p[n][mine] for n in p if "_experts_" in n}}, x,
                "l1", {**cfg, "num_experts": held, "expert_offset": offset})
            np.testing.assert_allclose(share, want, rtol=1e-4, atol=1e-5)
            total = total + share
    np.testing.assert_allclose(total, uncut, rtol=1e-4, atol=1e-5)


def test_route_under_both_rules_the_sigmoid_one_bit_for_bit_what_it_was():
    rng = np.random.RandomState(8)
    x = jnp.asarray(rng.randn(50, 32), jnp.float32)
    w = jnp.asarray(rng.randn(32, 16) * 0.3, jnp.float32)
    bias = jnp.asarray(rng.uniform(-0.2, 0.2, 16), jnp.float32)

    def as_it_was(x, w, bias, top_k, scaling, normalize):   # PR 31's route
        with jax.default_matmul_precision("highest"):
            scores = jax.nn.sigmoid(jnp.matmul(
                x.astype(jnp.float32), w.astype(jnp.float32)))
        _, idx = jax.lax.top_k(
            jax.lax.stop_gradient(scores + bias.astype(jnp.float32)), top_k)
        weight = jnp.take_along_axis(scores, idx, axis=-1)
        if normalize:
            weight = weight / (jnp.sum(weight, axis=-1, keepdims=True)
                               + 1e-20)
        return idx.astype(jnp.int32), weight * scaling

    for normalize in (True, False):
        idx, weight, load = moe_ops.route(x, w, bias, 3, 2.446, normalize)
        want_idx, want_weight = as_it_was(x, w, bias, 3, 2.446, normalize)
        np.testing.assert_array_equal(idx, want_idx)
        np.testing.assert_array_equal(weight, want_weight)
        assert float(load.sum()) == 150
    # the softmax rule: no bias, the softmax over ALL experts, then the
    # chosen ones' share of their own sum
    idx, weight, _ = moe_ops.route(x, w, None, 3, 1.0, True,
                                   scoring="softmax")
    with jax.default_matmul_precision("highest"):
        s = jax.nn.softmax(x @ w, axis=-1)
    _, want_idx = jax.lax.top_k(s, 3)
    top = jnp.take_along_axis(s, want_idx, axis=-1)
    np.testing.assert_array_equal(idx, want_idx)
    np.testing.assert_allclose(weight, top / top.sum(-1, keepdims=True),
                               rtol=1e-6)
    raw = moe_ops.route(x, w, None, 3, 1.0, False, scoring="softmax")[1]
    np.testing.assert_allclose(raw, top, rtol=1e-6)


def test_rotary_turns_each_section_by_its_own_stream():
    """Pairs of section j turn by stream j; equal streams are the one-axis
    rotary the op always was, bit for bit."""
    rng = np.random.RandomState(9)
    x = jnp.asarray(rng.randn(2, 3, 8, 16), jnp.float32)
    pos = jnp.asarray(_unequal_positions(2, 3, 8))
    got = attention_ops._rotate(x, 1e4, positions=pos, sections=(2, 2, 4))
    inv = 1e4 ** (-np.arange(8) * 2.0 / 16)
    stream = np.repeat(np.arange(3), (2, 2, 4))
    angle = np.asarray(pos, np.float64).transpose(0, 2, 1)[..., stream] * inv
    cos, sin = np.cos(angle)[:, None], np.sin(angle)[:, None]
    x1, x2 = np.asarray(x[..., :8]), np.asarray(x[..., 8:])
    want = np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    same = jnp.broadcast_to(jnp.arange(8, dtype=jnp.int32), (2, 3, 8))
    np.testing.assert_allclose(
        attention_ops._rotate(x, 1e4, positions=same, sections=(2, 2, 4)),
        attention_ops._rotate(x, 1e4), rtol=1e-6, atol=1e-6)
    with pytest.raises(ValueError):
        attention_ops._rotate(x, 1e4, positions=pos, sections=(2, 2, 2))


sys.path.insert(0, os.path.join(REPO, "tools"))
import keye_reference_probe as probe  # noqa: E402

MUTANT_TOL = {"loss_rtol": 1e-4, "grad_cos_min": 0.9999,
              "grad_norm_rtol": 1e-3, "param_norm_factor": 1.01}


@pytest.fixture(scope="module")
def one_step():
    """One forward-backward pass at the tiny size, the router's weights
    large enough for its rule to matter."""
    return _built(expert_offset=0, experts_held=16)


@pytest.mark.parametrize("name", (None, "index_from_bf16") + probe.MUTANTS)
def test_the_reference_refuses_each_mutant(one_step, name):
    """Every mutant tools/keye_reference_probe.py holds the chip's first
    step to, at the tiny size on unequal streams: the program against the
    reference is inside the rehearsal's tolerances, against each mutant
    outside at least one (fp8 matmuls included)."""
    from benchmark.harness import reference as harness_reference

    spec, params, batch, grads, loss = one_step
    cfg = _ref_cfg(spec.extras["config"])
    cfg = {**cfg, "sa_config": {**cfg["sa_config"], "topk": 8}}
    ref_loss, ref_grads = as_one_compile(
        probe.mutant(name), params, batch, cfg,
        feed_names=tuple(spec.feed_names), trainable=frozenset(grads),
        micro=1)
    prods = {k: (float(np.vdot(grads[k], ref_grads[k])),
                 float(np.vdot(grads[k], grads[k])),
                 float(np.vdot(ref_grads[k], ref_grads[k])))
             for k in grads}
    found = harness_reference.judge(loss, float(ref_loss), prods)
    problems = harness_reference.problems(found, MUTANT_TOL)
    if name in (None,):
        assert not problems, problems
    elif name == "index_from_bf16":     # not wrong: a rounding, near by
        assert found["grad_cos"] > 0.99, found
    else:
        assert problems, (name, found)


def test_the_mutants_are_the_probes_and_an_unknown_one_is_an_error():
    assert len(probe.MUTANTS) == 10 and "fp8_matmuls" in probe.MUTANTS
    with pytest.raises(KeyError):
        probe.mutant("no_such_mutant")


def _spans_of_a_step(names, **over):
    """The named spans' counts from one training step lowered abstractly
    for the TPU (nothing compiles or runs)."""
    observability.reset()
    fluid.flags._VALUES["FLAGS_observability"] = True
    try:
        fluid.reset_default_env()
        cfg = models.SparseDecoderConfig(**{**TINY, **over})
        spec = models.sparse_decoder(cfg)
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


def test_dsa_lower_and_moe_lower_say_what_a_site_was_given():
    """At the cell's attention shape, its index and its router's counts, at
    a model width cut down: one dsa.lower and one moe.lower a layer (a
    layer is lowered once: its recomputation is jax's)."""
    S = 16384
    spans = _spans_of_a_step(
        ("dsa.lower", "moe.lower"), max_length=S, n_layer=2, n_head=32,
        n_kv_head=4, head_dim=128, mrope_section=(16, 24, 24),
        index_heads=16, index_dim=64, index_topk=2048, q_chunk=512,
        kv_chunk=512, n_routed_experts=128, experts_held=16,
        expert_offset=0, top_k=8)
    assert spans["dsa.lower"] == 2 * [dict(
        heads=32, kv_heads=4, index_heads=16, index_dim=64, topk=2048,
        sq=S, q_chunk=512, kv_chunk=1024, keys_causal=S * (S + 1) // 2,
        keys_selected=2048 * 2049 // 2 + (S - 2048) * 2048,
        engine="masked-block", indices="recomputed", kept="out,lse,thr",
        kept_bytes=S * (32 * 128 * 2 + 32 * 4 + 4))]
    assert spans["dsa.lower"][0]["keys_selected"] == 31_458_304
    assert spans["dsa.lower"][0]["kept_bytes"] == 136_380_416
    assert spans["moe.lower"] == 2 * [dict(
        experts_total=128, experts_held=16, top_k=8, row_buffer=8 * S,
        row_buffer_usual=2 * S, row_buffers=3, engine="megablox",
        combine="tgmm", feature_rows=7 * 2 * S, dropped=0,
        scoring="softmax")]


def test_the_residual_writers_start_scaled_by_the_published_depth():
    """attention's o and the experts' down at init_std / sqrt(2 x layers),
    every other matrix at init_std (configuration `assumed.init`)."""
    fluid.reset_default_env()
    spec = models.sparse_decoder(models.SparseDecoderConfig(
        **{**TINY, "d_model": 64, "d_expert": 64, "residual_init_layers": 8}))
    fluid.Executor(fluid.CPUPlace()).run(fluid.default_startup_program())
    scope = fluid.global_scope()
    stds = {p.name: float(np.asarray(scope.find_var(p.name)).std())
            for p in fluid.default_main_program().all_parameters()
            if p.name.endswith("_w")}
    for name, std in stds.items():
        want = 0.02 / 4 if name.endswith(("_attn_o_w", "_down_w")) else 0.02
        assert std == pytest.approx(want, rel=0.15), name
    assert spec.extras["config"].residual_init_layers == 8
