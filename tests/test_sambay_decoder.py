"""models/sambay_decoder.py (Phi-4-mini-flash-reasoning's decoder-hybrid-
decoder: Mamba-1 mixers, sliding, full and cross differential attention,
Gated Memory Units, a memory and a key/value pair handed from one
recomputed unit to later ones, LayerNorm, a tied head) against its plain
reference, benchmark/configs/phi-4-mini-flash.reference.py, at tiny sizes on
the CPU: loss, logits and every named gradient; the layout at the published
depth and at the cut's; the handed values' cotangents summed over their
readers, with and without recomputation, each value lowered once; the eight
vocabulary slices' logits against the uncut model's; every wrong rule
tools/sambay_reference_probe.py holds the chip's first step to, refused at
the rehearsal's limits; the flash kernels at head 64 reading values 128
wide; and the step as it lowers for a TPU (one scan forward and one
backward a Mamba layer, no score array); and the MLPs' first product, which
`layers.kept` holds through a layer's recomputation: the untagged program's
loss and gradients bit for bit, one [B, S, 2 d_inner] product a layer in the
TPU's step where the untagged one has two, no operation without
recomputation, bf16 kept bf16."""

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
from benchmark.harness import manifest
from benchmark.harness import reference as harness_reference
from decoder_steps import (
    as_one_compile, first_products, kept_adds_no_operation_without_recompute,
    kept_is_the_untagged_program_bit_for_bit, kept_products_are_lowered_once,
    once_a_program)
from paddle_tpu import models
flash = importlib.import_module("paddle_tpu.kernels.flash_attention")

# (the package's `sambay_decoder` is the function)
sambay = importlib.import_module("paddle_tpu.models.sambay_decoder")

import sambay_reference_probe as probe  # noqa: E402
from test_recompute_keep import _kernels, _step_for_the_tpu  # noqa: E402

TINY = dict(vocab_size=40, max_length=48, d_model=32, d_inner=48, n_head=4,
            n_kv_head=2, sliding_window=8, d_state=8, dt_rank=4)
RTOL, ATOL = 2e-4, 2e-5
REF = probe.mutant(None)
LIMITS = manifest.read_json(os.path.join(
    REPO, "benchmark", "configs", "phi-4-mini-flash.json"))["rehearsal"][
        "reference"]


def _ref_cfg(cfg: models.SambaYDecoderConfig) -> dict:
    return {"hidden_size": cfg.d_model, "intermediate_size": cfg.d_inner,
            "num_attention_heads": cfg.n_head,
            "num_key_value_heads": cfg.n_kv_head,
            "sliding_window": cfg.sliding_window,
            "self_decoder_periods": cfg.self_periods,
            "cross_decoder_periods": cfg.cross_periods,
            "mamba_expand": cfg.expand, "mamba_d_state": cfg.d_state,
            "mamba_dt_rank": cfg.dt_rank,
            "layer_norm_eps": cfg.layer_norm_eps,
            "reference": {"query_block": 20, "scan_block": 16,
                          "head_block": 20, "channel_block": 16}}


def _build(rows=2, ids_below=None, table_of=None, **over):
    """(spec, params, batch, gradients, loss, logits, states) of one
    forward-backward pass of a tiny model through the Executor, its
    parameters moved off their starts (tools/sambay_reference_probe.py's
    rule, and D, the biases and the convolution with opinions) so that
    which keys a query sees, which map it subtracts, what the scan adds and
    which memory a GMU reads all show in the gradient.  `ids_below`: the
    batch's ids come from the first so many rows of the table; `table_of`:
    every parameter is the model's whose table has that many rows (built
    with `ids_below` this one's rows), the table its first rows, and the
    batch that model's."""
    whole = table_of and _built(vocab_size=table_of,
                                ids_below=TINY["vocab_size"], **over)
    fluid.reset_default_env()
    cfg = models.SambaYDecoderConfig(**{**TINY, **over})
    spec = models.sambay_decoder(cfg)
    pairs = fluid.append_backward(spec.loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    scope = fluid.global_scope()
    rng = np.random.default_rng(11)
    kv = cfg.n_kv_head * (cfg.d_model // cfg.n_head)
    for p in fluid.default_main_program().all_parameters():
        v = np.asarray(scope.find_var(p.name))
        new = probe.moved(p.name, v, rng, cfg.d_model, kv)
        if new is None and p.name.endswith(("_b", "_bias", "_ssm_d")):
            new = v + 0.3 * rng.standard_normal(v.shape)
        if new is None and p.name.endswith(("_w",)) and "conv" not in p.name:
            new = v * 8
        if whole:
            new = whole[1][p.name][:v.shape[0]]
        if new is not None:
            scope.set_var(p.name, new.astype(np.float32))
    params = {p.name: np.asarray(scope.find_var(p.name))
              for p in fluid.default_main_program().all_parameters()}
    batch = whole[2] if whole else spec.synthetic_batch(rows, seed=5)
    if ids_below:
        batch = {k: v % ids_below for k, v in batch.items()}
    got = exe.run(feed=batch, fetch_list=[
        spec.loss, spec.extras["logits"], spec.extras["states"]]
        + [g for _, g in pairs])
    grads = {p.name: np.asarray(g) for (p, _), g in zip(pairs, got[3:])}
    return (spec, params, batch, grads, float(np.ravel(got[0])[0]),
            np.asarray(got[1]), np.asarray(got[2]))


_built = once_a_program(_build)


@functools.lru_cache(None)
def _reference_of(key, wrong=None):
    spec, params, batch, grads = _built(**dict(key))[:4]
    loss, grad = as_one_compile(
        probe.mutant(wrong).loss_and_grad, params, batch,
        _ref_cfg(spec.extras["config"]), tuple(spec.feed_names),
        frozenset(grads), 1)
    return float(loss), {k: np.asarray(v) for k, v in grad.items()}


# the cut; two readers of the memory and of K, V each; the same with the
# units not recomputed, on a row that is not whole chunks of the scan nor
# whole blocks of queries
PROGRAMS = [{}, {"cross_periods": 2},
            {"cross_periods": 2, "use_recompute": False, "max_length": 37,
             "sliding_window": 5}]
_ids = dict(ids=lambda o: ",".join(f"{k}={v}" for k, v in o.items())
            or "the_cut")


@pytest.mark.parametrize("over", PROGRAMS, **_ids)
def test_the_program_builds_and_runs(over):
    """One forward-backward pass through the Executor (the other tests read
    what it returned): a finite loss near log(vocabulary), every parameter
    with a gradient of its own shape."""
    spec, params, _, grads, loss = _built(**over)[:5]
    assert np.isfinite(loss) and 2.0 < loss < 8.0
    assert set(grads) == set(params)
    assert all(grads[k].shape == params[k].shape for k in params)
    assert spec.extras["config"].n_layer == (8 if over else 6)


@pytest.mark.parametrize("over", PROGRAMS, **_ids)
def test_program_against_the_plain_reference(over):
    """The loss and every parameter's gradient."""
    grads, loss = _built(**over)[3:5]
    ref_loss, ref_grads = _reference_of(tuple(sorted(over.items())))
    assert loss == pytest.approx(ref_loss, rel=RTOL)
    assert set(grads) == set(ref_grads)
    for name in sorted(ref_grads):
        scale = max(np.abs(ref_grads[name]).max(), 1.0)
        np.testing.assert_allclose(grads[name], ref_grads[name], rtol=RTOL,
                                   atol=ATOL * scale, err_msg=name)
        assert np.abs(ref_grads[name]).max() > 0, name


@pytest.mark.parametrize("over", PROGRAMS, **_ids)
def test_logits_against_the_plain_reference(over):
    spec, params, batch = _built(**over)[:3]
    logits = _built(**over)[5]
    cfg = _ref_cfg(spec.extras["config"])
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.vmap(lambda t: REF._logits(
            {k: jnp.asarray(v) for k, v in params.items()}, t, cfg)))(
                jnp.asarray(batch[spec.feed_names[0]]))
    np.testing.assert_allclose(logits[0], want, rtol=RTOL, atol=2e-4)


def test_the_layout_at_the_published_depth_and_at_the_cuts():
    kinds = sambay.layer_kinds(32)
    assert [kinds.count(k) + (k == "mamba") * kinds.count("memory")
            for k in ("mamba", "sliding", "full", "gmu", "cross")] \
        == [9, 8, 1, 7, 7]
    assert kinds[:16] == ("mamba", "sliding") * 8
    assert kinds[16:18] == ("memory", "full")
    assert kinds[18:] == ("gmu", "cross") * 7
    assert kinds == sambay.layer_kinds(None, 8, 7)
    assert models.SambaYDecoderConfig().kinds == (
        "mamba", "sliding", "memory", "full", "gmu", "cross")
    with pytest.raises(ValueError):
        sambay.layer_kinds(30)
    assert [round(sambay.lambda_init(i), 4) for i in (0, 1, 5)] \
        == [0.2, 0.3555, 0.6661]


def _grad_ops_of(program, name):
    """The backward's ops that write `name`'s gradient, renamed parts among
    them."""
    return [op for op in program.global_block().desc.ops
            if any(n.split("@RENAME@")[0] == name + "@GRAD"
                   for n in op.output_arg_names())]


@pytest.mark.parametrize("recompute", [True, False])
def test_a_handed_values_cotangent_is_the_sum_over_its_readers(recompute):
    """With two GMUs and two cross layers: the memory and K, V each come
    out of ONE recurrence, enter two later ones from outside their bodies
    (among the `X` a body reads, as a parameter does, and not a carry), and
    the backward writes their gradient as two readers' parts, their `sum`
    and its `assign` to the name the producer's recurrence_grad reads.
    That the numbers are right is
    test_program_against_the_plain_reference's."""
    spec = _built(**PROGRAMS[1 if recompute else 2])[0]
    program = spec.loss.block.program
    block = program.global_block()
    ops = list(block.desc.ops)
    handed = [spec.extras["memory"], *spec.extras["shared"]]
    for var in handed:
        stacked = next(op for op in ops if op.type == "squeeze2" or
                       op.type == "squeeze" if var.name
                       in op.output_arg_names()).input_arg_names()[0]
        makers = [op for op in ops if op.type == "recurrence"
                  and stacked in op.outputs["Out"]]
        readers = [op for op in ops if op.type == "recurrence"
                   and var.name in op.inputs["X"]]
        assert len(makers) == 1 and len(readers) == 2
        assert all(var.name not in op.inputs["Init"] for op in readers)
        writers = _grad_ops_of(program, var.name)
        assert sorted(op.type for op in writers) \
            == ["assign", "recurrence_grad", "recurrence_grad", "sum"]
        total = next(op for op in writers if op.type == "sum")
        assert len(total.inputs["X"]) == 2
    assert sum(op.type == "handed_on" for b in program.blocks
               for op in b.desc.ops) == 3


def test_the_handed_values_are_lowered_once():
    """One `shared.lower` a handed value and one `ssm.lower` a Mamba layer
    in a traced step with two readers each: no reader's recomputation makes
    the memory, K or V again."""
    from paddle_tpu import observability

    fluid.flags._VALUES["FLAGS_observability"] = True
    try:
        observability.reset()
        _build(cross_periods=2)
        spans = [(s.name, dict(s.args)) for s in
                 observability.default_tracer().spans()
                 if s.name in ("shared.lower", "ssm.lower")]
    finally:
        fluid.flags._VALUES["FLAGS_observability"] = False
        observability.reset()
    shared = [a for n, a in spans if n == "shared.lower"]
    S, E, kv = TINY["max_length"], 64, 16
    assert shared == [
        dict(what="memory", bytes=2 * S * E * 4, readers=2),
        dict(what="kv", bytes=2 * S * kv * 4, readers=2),
        dict(what="kv", bytes=2 * S * kv * 4, readers=2)]
    assert len([n for n, _ in spans if n == "ssm.lower"]) == 2


@pytest.mark.parametrize("part", range(8))
def test_the_eight_vocabulary_slices_logits_are_the_uncut_models(part):
    """The uncut table is 8 x the rows held; the ids come from slice 0, as
    the cell's do.  Every slice's logits are the final states against that
    slice's rows of the tied table, and the eight side by side are the
    uncut model's."""
    held = TINY["vocab_size"]
    whole = _built(vocab_size=8 * held, ids_below=held)
    table, logits, states = whole[1]["embed"], whole[5], whole[6]
    rows = slice(part * held, (part + 1) * held)
    np.testing.assert_allclose(
        logits[0][..., rows], states[0] @ table[rows].T, rtol=1e-4, atol=1e-5)


def test_the_program_with_slice_0_is_the_uncut_program_over_its_rows():
    """The program built with the first eighth of the table (every other
    parameter the uncut model's) gives the uncut program's final states,
    and its logits are the uncut logits' first columns."""
    held = TINY["vocab_size"]
    whole = _built(vocab_size=8 * held, ids_below=held)
    mine = _built(table_of=8 * held)
    np.testing.assert_allclose(mine[6], whole[6], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(mine[5], whole[5][..., :held], rtol=1e-4,
                               atol=1e-5)


@pytest.mark.parametrize("wrong", probe.MUTANTS)
def test_a_wrong_rule_is_refused_at_the_rehearsals_limits(wrong):
    """Each the reference with one thing wrong, judged as the benchmark's
    first step is (benchmark/harness/reference.py), at the limits of the
    configuration's rehearsal: refused, where the reference itself
    passes."""
    key = (("cross_periods", 2),)
    grads, loss = _built(**dict(key))[3:5]

    def found(name):
        ref_loss, ref = _reference_of(key, name)
        prods = {k: (float(np.vdot(grads[k], ref[k])),
                     float(np.vdot(grads[k], grads[k])),
                     float(np.vdot(ref[k], ref[k]))) for k in ref}
        return harness_reference.problems(
            harness_reference.judge(loss, ref_loss, prods), LIMITS)

    assert not found(None)
    assert found(wrong), wrong


@pytest.mark.parametrize("window", [None, 5])
def test_the_op_differential_attention_against_the_dense_maps(window):
    """`layers.differential_attention` alone in a program (and behind it
    `layers.handed_on`, which hands its input on as it is), 4 heads of 8
    over 2: the output and the gradients of q, k, v, the four lambda
    vectors and the pair's scale against the reference's dense masked
    maps."""
    from paddle_tpu import layers

    S, H, G, D, layer = 16, 4, 2, 8, 3
    r = np.random.RandomState(7)
    shapes = {"q": (1, S, H * D), "k": (1, S, G * D), "v": (1, S, G * D),
              "lambda_q1": (D,), "lambda_k1": (D,), "lambda_q2": (D,),
              "lambda_k2": (D,), "scale": (2 * D,), "w": (1, S, H * D)}
    feed = {n: r.randn(*shape).astype(np.float32)
            for n, shape in shapes.items()}
    fluid.reset_default_env()
    ins = {n: layers.data(n, list(shape), dtype="float32",
                          append_batch_size=False)
           for n, shape in shapes.items()}
    for t in ins.values():
        t.stop_gradient = False
    *operands, weight = ins.values()
    out = layers.handed_on(layers.differential_attention(
        *operands, n_head=H, lambda_init=sambay.lambda_init(layer),
        window=window, epsilon=1e-5), "kv", 1)
    loss = layers.reduce_sum(layers.elementwise_mul(out, weight))
    got = fluid.Executor(fluid.CPUPlace()).run(
        feed=feed, fetch_list=[out] + list(fluid.calc_gradient(
            loss, operands)))

    def dense(q, k, v, lq1, lk1, lq2, lk2, scale):
        p = {"a_lambda_q1": lq1, "a_lambda_k1": lk1, "a_lambda_q2": lq2,
             "a_lambda_k2": lk2, "a_subln_scale": scale}
        cfg = {"num_attention_heads": H, "num_key_value_heads": G,
               "hidden_size": H * D, "layer_norm_eps": 1e-5,
               "reference": {"query_block": 6}}
        return REF._diff_attention(p, q[0], k[0], v[0], "a", layer, window,
                                   cfg)[None]

    args = [jnp.asarray(feed[n]) for n in list(shapes)[:-1]]
    with jax.default_matmul_precision("highest"):
        want, pull = jax.vjp(dense, *args)
        grads = pull(jnp.asarray(feed["w"]))
    for a, b in zip(got, (want,) + tuple(grads)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


# (S, window): the band of PR 59, and every causal key
SHAPES = {"band": (256, 64), "full": (256, None)}


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_flash_attention_at_head_64_reading_values_128_wide(shape):
    """20 heads over 10 at the cell's widths (4 over 2 here), the kernels
    in the interpreter against dense masked scores: the output and the
    three gradients."""
    S, window = SHAPES[shape]
    r = np.random.RandomState(4)
    q, k, v = (jnp.asarray(r.randn(1, h, S, d), jnp.float32)
               for h, d in ((4, 64), (2, 64), (2, 128)))
    w = jnp.asarray(r.randn(1, 4, S, 128), jnp.float32)

    def dense(q, k, v):
        scores = jnp.einsum("bhqd,bhsd->bhqs", q, jnp.repeat(k, 2, 1)) / 8.0
        t, s = jnp.arange(S)[:, None], jnp.arange(S)[None]
        sees = (s <= t) & ((t - s < window) if window else True)
        p = jax.nn.softmax(jnp.where(sees, scores, -1e30), -1)
        return jnp.einsum("bhqs,bhsw->bhqw", p, jnp.repeat(v, 2, 1))

    def kernels(q, k, v):
        return flash.flash_attention(q, k, v, causal=True, window=window,
                                     force="interpret")

    got, pull = jax.vjp(kernels, q, k, v)
    want, ref_pull = jax.vjp(dense, q, k, v)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    for a, b in zip(pull(w), ref_pull(w)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


def test_the_step_as_it_lowers_for_a_tpu():
    """At channels that tile (d 512: 1024 channels, 8 heads of 64 over 4)
    and S 384: the selective scan's kernel pair once a Mamba layer, forward
    AND backward (the units are recomputed: no second forward), two flash
    sites a differential attention, the sliding layer's on the band; no
    [S, S] array and no state a token anywhere in the step."""
    S = 384
    cfg = models.SambaYDecoderConfig(
        vocab_size=64, max_length=S, d_model=512, d_inner=320, n_head=8,
        n_kv_head=4, sliding_window=128, dt_rank=8)
    text, spans = _step_for_the_tpu(
        models.sambay_decoder, cfg,
        span_names=("ssm.lower", "attn.lower", "shared.lower",
                    "recurrence.lower"))
    # the scan's y and starts, two flash sites' out and lse, and W1's output
    assert [s["kept"] for s in spans["recurrence.lower"]] == [3, 5, 3, 5, 1, 5]
    assert first_products(text, S, 2 * cfg.d_inner) == cfg.n_layer
    calls = _kernels(text)
    assert calls["_fwd_kernel"] == 2 and calls["_bwd_kernel"] == 2
    assert calls["_band_kernel"] == 2 and calls["_band_bwd_kernel"] == 2
    assert calls["_flash_kernel"] == 4
    assert [s["engine"] for s in spans["ssm.lower"]] == ["pallas"] * 2
    assert [s["kind"] for s in spans["attn.lower"]] \
        == ["sliding"] * 2 + ["full"] * 4
    assert all(s["kept"] == "out,lse" for s in spans["attn.lower"])
    assert [s["what"] for s in spans["shared.lower"]] \
        == ["memory", "kv", "kv"]
    assert not re.search(rf"tensor<[0-9x]*{S}x{S}x", text)
    assert not re.search(rf"tensor<[0-9x]*{S}x1024x16x", text)
    assert not re.search(rf"tensor<[0-9x]*{S}x16x1024x", text)


# ---------------------------------------------------------------------------
# the MLP's first product survives its layer's recomputation (layers.kept)
# ---------------------------------------------------------------------------
KEPT = (models.sambay_decoder, models.SambaYDecoderConfig)


@pytest.mark.parametrize("recompute", [True, False])
def test_the_tagged_programs_loss_and_gradients_are_the_untagged_ones(
        recompute):
    kept_is_the_untagged_program_bit_for_bit(
        *KEPT, tags=6, least=80, **TINY, use_recompute=recompute)


def test_w1s_product_is_lowered_once_a_layer_where_the_untagged_step_has_two():
    kept_products_are_lowered_once(
        *KEPT, widths={2 * TINY["d_inner"]: 6}, kept_a_unit=[1] * 6, **TINY)


def test_without_recompute_the_tag_adds_no_operation():
    kept_adds_no_operation_without_recompute(
        *KEPT, kept_a_unit=[1] * 6, **TINY)


@pytest.mark.parametrize("tier", ["keep", "mxu", "off"])
def test_the_op_kept_hands_its_input_on_in_its_own_dtype(tier):
    """`layers.kept` behind a product under each AMP tier: the product's
    own output, bit for bit and in its dtype (bf16 on the keep tier, the
    cell's), and the gradient of what it read."""
    from paddle_tpu import layers
    from paddle_tpu.core import amp

    r = np.random.RandomState(3)
    feed = {"x": r.randn(2, 8, 16).astype(np.float32),
            "w": r.randn(16, 24).astype(np.float32)}

    def run(tagged):
        fluid.reset_default_env()
        x, w = (layers.data(n, list(v.shape), dtype="float32",
                            append_batch_size=False) for n, v in feed.items())
        x.stop_gradient = w.stop_gradient = False
        y = layers.matmul(x, w)
        y = layers.kept(y) if tagged else y
        loss = layers.reduce_sum(layers.square(y))
        return fluid.Executor(fluid.CPUPlace()).run(
            feed=feed, fetch_list=[y] + list(fluid.calc_gradient(
                loss, [x, w])), return_numpy=False)

    if tier != "off":
        amp.enable_amp("bfloat16", keep_output=(tier == "keep"))
    try:
        got, want = run(True), run(False)
    finally:
        amp.reset_amp()
    assert jnp.asarray(got[0]).dtype == (
        jnp.bfloat16 if tier == "keep" else jnp.float32)
    for a, b in zip(got, want):
        a, b = jnp.asarray(a), jnp.asarray(b)
        assert a.dtype == b.dtype and np.array_equal(a, b)
