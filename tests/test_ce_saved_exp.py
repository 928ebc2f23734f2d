"""softmax_with_cross_entropy's hard-label path (PR 32): one exponential pass,
saved in the logits' dtype for a backward that evaluates no exp
(ops/loss_ops.py::_hard_ce), held to jax's own gradient of the formulation
the op had before; soft labels keep that formulation.  Values and counts on
the CPU, never times; the chip-less compile of the head is in
tests/test_aot_cost.py (one file loads the TPU compiler).
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, observability
from paddle_tpu.core.registry import OpRegistry
from paddle_tpu.ops import loss_ops


def _old(logits, label, soft_label=False, smooth_eps=0.0, ignore_index=-100):
    """The op's lowering as it stood before PR 32, whole: (Softmax, Loss)."""
    logp = jax.nn.log_softmax(
        logits.astype(loss_ops.amp.stats_dtype(logits)), axis=-1)
    softmax = jnp.exp(logp)
    if soft_label:
        loss = -jnp.sum(label * logp, axis=-1, keepdims=True)
    else:
        lab = label
        if lab.ndim == logits.ndim:
            lab = jnp.squeeze(lab, axis=-1)
        loss = -jnp.take_along_axis(
            logp, lab[..., None].astype(jnp.int32), axis=-1)
        if smooth_eps:
            loss = (1.0 - smooth_eps) * loss - smooth_eps * jnp.mean(
                logp, axis=-1, keepdims=True)
        loss = jnp.where((lab != ignore_index)[..., None], loss, 0.0)
    return softmax.astype(logits.dtype), loss.astype(logits.dtype)


def _op(logits, label, **attrs):
    """The registered lowering, outside any program: (Softmax, Loss)."""
    ctx = types.SimpleNamespace(cur_op=None)
    out = OpRegistry.get("softmax_with_cross_entropy").lower(
        ctx, {"Logits": [logits], "Label": [label]}, attrs)
    return out["Softmax"][0], out["Loss"][0]


def _case(seed, shape, dtype, ignore_rows, trailing_one):
    rng = np.random.RandomState(seed)
    logits = jnp.asarray(3.0 * rng.randn(*shape), dtype)
    lab = rng.randint(0, shape[-1], shape[:-1])
    if ignore_rows:
        lab[rng.rand(*lab.shape) < 0.3] = -100
        lab.flat[0] = -100
    lab = jnp.asarray(lab[..., None] if trailing_one else lab, jnp.int32)
    weight = jnp.asarray(rng.rand(*shape[:-1], 1) + 0.5, jnp.float32)
    return logits, lab, weight


def _cosine(a, b):
    a, b = (np.asarray(x, np.float64).ravel() for x in (a, b))
    return float(a @ b / (np.linalg.norm(a) * np.linalg.norm(b)))


VARIANTS = {"hard": {}, "smooth": {"smooth_eps": 0.1},
            "ignore": {"ignore_rows": True},
            "smooth+ignore": {"smooth_eps": 0.1, "ignore_rows": True}}


@pytest.mark.parametrize("shape", [(24, 50), (3, 8, 50), (2, 3, 4, 130)],
                         ids=["2d", "3d", "4d"])
@pytest.mark.parametrize("variant", list(VARIANTS))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_loss_and_gradient_equal_jaxs_of_the_old_formulation(
        shape, variant, dtype):
    """fp32 to 1e-6; bf16 (one more rounding of the saved array, 2^-9) by
    the gradient's cosine and norm.  Rows at ignore_index give 0 and take
    no gradient; the 3-D case names its labels [..., 1], the others [...]."""
    attrs = dict(VARIANTS[variant])
    ignore_rows = attrs.pop("ignore_rows", False)
    logits, lab, weight = _case(len(shape) * 7 + len(variant), shape, dtype,
                                ignore_rows, trailing_one=len(shape) == 3)

    def total(fn):
        def f(x):
            return jnp.sum(fn(x, lab, **attrs)[1].astype(jnp.float32)
                           * weight)
        return f

    (want_l, want_g), (got_l, got_g) = (
        jax.value_and_grad(total(fn))(logits) for fn in (_old, _op))
    assert got_g.dtype == logits.dtype
    if dtype == "float32":
        np.testing.assert_allclose(_op(logits, lab, **attrs)[1],
                                   _old(logits, lab, **attrs)[1],
                                   rtol=1e-6, atol=2e-6)
        np.testing.assert_allclose(got_g, want_g, rtol=1e-6, atol=1e-6)
    else:
        assert abs(float(got_l) - float(want_l)) <= 4e-3 * abs(float(want_l))
        g32 = jax.grad(total(_old))(logits.astype(jnp.float32))
        assert _cosine(got_g, g32) >= 0.9999
        assert _cosine(got_g, want_g) >= 0.9999
        ratio = (np.linalg.norm(np.asarray(got_g, np.float64))
                 / np.linalg.norm(np.asarray(g32, np.float64)))
        assert abs(ratio - 1.0) < 2e-3
    if ignore_rows:
        dead = np.asarray(lab).reshape(shape[:-1]) == -100
        assert dead.any()
        assert not np.asarray(got_g, np.float32)[dead].any()
        assert not np.asarray(_op(logits, lab, **attrs)[1],
                              np.float32)[dead].any()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_softmax_output_and_its_own_gradient(dtype):
    """Softmax is e / s in the logits' dtype, and a program that
    differentiates through it gets softmax's vjp beside the loss's."""
    logits, lab, weight = _case(5, (12, 40), dtype, True, False)
    mix = jnp.asarray(np.random.RandomState(6).randn(12, 40), jnp.float32)

    def total(fn):
        def f(x):
            sm, loss = fn(x, lab, smooth_eps=0.1)
            return (jnp.sum(loss.astype(jnp.float32) * weight)
                    + jnp.sum(sm.astype(jnp.float32) * mix))
        return f

    tol = 1e-6 if dtype == "float32" else 1e-2
    np.testing.assert_allclose(
        np.asarray(_op(logits, lab)[0], np.float32),
        np.asarray(_old(logits, lab)[0], np.float32), rtol=tol, atol=tol)
    want = jax.grad(total(_old))(logits.astype(jnp.float32))
    got = jax.grad(total(_op))(logits)
    if dtype == "float32":
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    else:
        assert _cosine(got, want) >= 0.9995


def test_soft_labels_keep_jaxs_gradient_bit_for_bit():
    rng = np.random.RandomState(3)
    for dtype in ("float32", "bfloat16"):
        logits = jnp.asarray(rng.randn(6, 5, 30), dtype)
        soft = jax.nn.softmax(jnp.asarray(rng.randn(6, 5, 30), jnp.float32))

        def total(fn):
            return lambda x: jnp.sum(
                fn(x, soft, soft_label=True)[1].astype(jnp.float32) ** 2)

        spans = _spans(lambda: _op(logits, soft, soft_label=True))
        assert [s["path"] for s in spans] == ["autodiff"]
        assert spans[0]["soft_label"] is True and not spans[0]["pinned_bytes"]
        for got, want in zip(_op(logits, soft, soft_label=True),
                             _old(logits, soft, soft_label=True)):
            np.testing.assert_array_equal(np.asarray(got, np.float32),
                                          np.asarray(want, np.float32))
        np.testing.assert_array_equal(
            np.asarray(jax.grad(total(_op))(logits), np.float32),
            np.asarray(jax.grad(total(_old))(logits), np.float32))


def _spans(fn, *args):
    """The args of the `ce.lower` spans that lowering `fn` leaves
    (abstractly: nothing compiles or runs)."""
    observability.reset()
    was = fluid.flags._VALUES["FLAGS_observability"]
    fluid.flags._VALUES["FLAGS_observability"] = True
    try:
        jax.eval_shape(fn, *args)
        return [dict(s.args) for s in observability.default_tracer().spans()
                if s.name == "ce.lower"]
    finally:
        fluid.flags._VALUES["FLAGS_observability"] = was
        observability.reset()


def test_ce_lower_span_fields():
    logits = jnp.zeros((4, 16, 96), jnp.bfloat16)
    lab = jnp.zeros((4, 16), jnp.int32)
    assert _spans(lambda: _op(logits, lab, smooth_eps=0.1)) == [dict(
        rows=64, classes=96, dtype="bfloat16", path="saved_exp",
        pinned_bytes=64 * 96 * 2, smooth_eps=0.1, soft_label=False)]
    assert _spans(lambda: _op(logits.astype(jnp.float32), lab)) == [dict(
        rows=64, classes=96, dtype="float32", path="saved_exp",
        pinned_bytes=64 * 96 * 4, smooth_eps=0.0, soft_label=False)]


def test_backward_evaluates_no_exponential_and_reads_the_pinned_array():
    """The jaxpr of the gradient: one exp (the forward's), one barrier, and
    no fp32 array of [rows, classes] among what the forward hands on."""
    logits = jnp.zeros((32, 64), jnp.bfloat16)
    lab = jnp.zeros((32,), jnp.int32)
    loss = lambda x: jnp.sum(_op(x, lab)[1].astype(jnp.float32))
    text = str(jax.make_jaxpr(jax.grad(loss))(logits))
    assert text.count(" exp ") == 1, text
    assert text.count("optimization_barrier") == 1
    _, vjp = jax.vjp(loss, logits)
    saved = [x for x in jax.tree_util.tree_leaves(vjp)
             if getattr(x, "shape", None) == (32, 64)]
    assert saved and all(x.dtype == jnp.bfloat16 for x in saved)


def _step_spans(spec_fn, feed_fn, with_text=True):
    """`ce.lower` spans and the lowered text of one training step, lowered
    abstractly for the TPU (`with_text` False: traced only; the expert
    layer's Pallas kernel refuses a toy width)."""
    fluid.reset_default_env()
    spec = spec_fn()
    fluid.optimizer.AdamOptimizer(learning_rate=1e-3).minimize(spec.loss)
    fluid.Executor(fluid.CPUPlace()).run(fluid.default_startup_program())
    text = []

    def lower():
        with fluid.flags.tpu_trace_scope(True):
            compiled, feed_vals, state_vals, rng = fluid.Executor(
                fluid.CPUPlace()).capture_program(
                    fluid.default_main_program(), feed=feed_fn(spec))
            traced = jax.jit(compiled.raw_fn).trace(
                feed_vals, state_vals, rng)
            text.append(traced.lower(lowering_platforms=("tpu",)).as_text(
                debug_info=True) if with_text else "")

    return _spans(lower), text[0]


def _looped():
    from paddle_tpu import models

    return models.looped_decoder(models.LoopedDecoderConfig(
        vocab_size=96, max_length=16, n_layer=1, n_head=2, head_dim=16,
        d_model=32, d_inner=64, loop_steps=3, exit_gate=True,
        use_recompute=True))


def _transformer():
    from paddle_tpu import models

    return models.transformer(models.TransformerConfig(
        src_vocab_size=64, trg_vocab_size=80, max_length=16, n_layer=1,
        n_head=2, d_model=32, d_inner=64, dropout=0.1, label_smooth_eps=0.1,
        use_flash_attention=False))


def _experts():
    from paddle_tpu import models

    return models.expert_decoder(models.ExpertDecoderConfig(
        vocab_size=64, max_length=16, n_layer=2, d_model=32, d_inner=64,
        n_head=2, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        kv_lora_rank=24, n_routed_experts=16, experts_held=4,
        expert_offset=4, top_k=3, d_expert=24))


def _tokens(spec):
    ids = np.zeros((2, 17), np.int64)
    tokens, labels = spec.feed_names
    return {tokens: ids[:, :-1], labels: ids[:, 1:]}


@pytest.mark.parametrize("build, feed, rows, classes, eps", [
    (_looped, _tokens, 3 * 2 * 16, 96, 0.0),
    (_transformer, lambda spec: spec.synthetic_batch(2, 0), None, 80, 0.1),
    (_experts, lambda spec: spec.synthetic_batch(2, 0), 2 * 16, 64, 0.0),
], ids=["looped_decoder", "transformer", "expert_decoder"])
def test_every_language_model_has_one_site_and_it_saves(
        build, feed, rows, classes, eps):
    """How often the mechanism engages: sites with path=saved_exp over all
    sites, 1 of 1 in the three models; under AMP on the TPU the pinned
    array is bf16, and the backward stays in the grad op's name scope."""
    spans, text = _step_spans(build, feed, with_text=build is not _experts)
    assert [s["path"] for s in spans] == ["saved_exp"]
    (site,) = spans
    assert site["classes"] == classes and site["smooth_eps"] == eps
    assert site["dtype"] == "bfloat16"
    assert site["pinned_bytes"] == 2 * site["rows"] * classes
    if rows is not None:
        assert site["rows"] == rows
    if build is _looped:  # loop_heads_ms.train reads this scope
        assert "loop.heads/softmax_with_cross_entropy_grad" in text


def test_a_program_on_probabilities_has_no_site():
    """resnet50 and mnist call `cross_entropy` on softmax's output: the op
    this PR changed is not in their step."""
    def build():
        img = layers.data("img", [1, 8, 8], dtype="float32")
        lab = layers.data("lab", [1], dtype="int64")
        prob = layers.fc(img, size=10, act="softmax")
        return types.SimpleNamespace(
            loss=layers.mean(layers.cross_entropy(prob, lab)))

    spans, text = _step_spans(build, lambda spec: {
        "img": np.zeros((4, 1, 8, 8), np.float32),
        "lab": np.zeros((4, 1), np.int64)})
    assert spans == [] and "optimization_barrier" not in text


@pytest.mark.parametrize("softmax_used", [False, True],
                         ids=["loss-only", "softmax-differentiated"])
def test_training_through_the_executor_follows_jaxs_gradient(softmax_used):
    """The op inside a program: the site asks its grad op whether Softmax
    has a gradient (`Softmax@GRAD`), and either way the logits' gradient a
    step computes is jax's of the old formulation."""
    fluid.reset_default_env()
    x = layers.data("x", [16], dtype="float32")
    lab = layers.data("lab", [1], dtype="int64")
    logits = layers.fc(x, size=10)
    loss, sm = layers.softmax_with_cross_entropy(
        logits, lab, return_softmax=True, smooth_eps=0.1)
    total = layers.mean(loss)
    if softmax_used:
        total = layers.elementwise_add(
            total, layers.mean(layers.elementwise_mul(sm, sm)))
    fluid.optimizer.SGDOptimizer(0.1).minimize(total)
    grad_op = [op for op in fluid.default_main_program().global_block()
               .desc.ops if op.type == "softmax_with_cross_entropy_grad"][0]
    assert any(grad_op.inputs["Softmax@GRAD"]) == softmax_used
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(0)
    feed = {"x": rng.randn(8, 16).astype("float32"),
            "lab": rng.randint(0, 10, (8, 1)).astype("int64")}
    z, got = exe.run(feed=feed,
                     fetch_list=[logits, logits.name + "@GRAD"])

    def ref(z):
        sm, loss = _old(z, jnp.asarray(feed["lab"]), smooth_eps=0.1)
        return jnp.mean(loss) + (jnp.mean(sm * sm) if softmax_used else 0.0)

    np.testing.assert_allclose(got, jax.grad(ref)(jnp.asarray(z)),
                               rtol=2e-5, atol=1e-7)
