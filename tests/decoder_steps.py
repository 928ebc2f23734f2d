"""What the decoder test files share: one build a distinct program, the
plain reference's step as one compile, and what holds a value that
`layers.kept` tags in a decoder whose layers really run their recomputation
(sambay_decoder, ssd_hybrid_decoder): the untagged program's loss and
gradients bit for bit, the tagged product lowered once a layer in the TPU's
step where the untagged step has two, bf16 kept bf16, no operation without
recomputation."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest


def once_a_program(build):
    """`build(*args, **over)` memoised on its arguments for as long as the
    module lives: a fixture and a case that ask for the same overrides share
    one trace, one compile and one run (~10 s of CPU each).  For callers
    that read what the build RETURNS and leave it as it is; one that goes on
    with the default program, the scope or the spans the build left behind
    calls the builder itself."""
    steps = {}

    @functools.wraps(build)
    def shared(*args, **over):
        key = (args, tuple(sorted(over.items())))
        if key not in steps:
            steps[key] = build(*args, **over)
        return steps[key]

    return shared


def as_one_compile(loss_and_grad, params, batch, *static, **named):
    """A plain reference's `loss_and_grad(params, batch, cfg, ...)` under
    one `jax.jit` (called eagerly it compiles its scan's forward and
    backward apart, and every cast before them): the same arithmetic, the
    configuration and the names closed over."""
    return jax.jit(lambda p, b: loss_and_grad(p, b, *static, **named))(
        {k: jnp.asarray(v) for k, v in params.items()},
        {k: jnp.asarray(v) for k, v in batch.items()})


# ---------------------------------------------------------------------------
# a value of plain ops survives its layer's recomputation (layers.kept)
# ---------------------------------------------------------------------------
def untagged(build, *args, **over):
    """`build` with `layers.kept` the identity function: the program before
    the tag."""
    from paddle_tpu import layers

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(layers, "kept", lambda x: x)
        return build(*args, **over)


def first_products(text, S, width) -> int:
    """dot_generals of a step's StableHLO whose result is the tokens by
    `width`: with `width` 2 d_inner W1's forward product and every
    recomputation of it (dW1's result is [d_model, 2 d_inner], dX's
    [B, S, d_model])."""
    return len(re.findall(
        rf"stablehlo\.dot_general.*-> tensor<1x{S}x{width}x\w+>", text))


def loss_and_gradients(model, config, **sizes):
    """(the `kept` ops of the program, the loss and every parameter's
    gradient) of `model(config(**sizes))`, through the Executor's own
    compiled block, with the CPU compiler's fusion off: fused, a
    multiply-add contracts or not by where a fusion ends, and the fusions of
    two steps that differ in one product a layer end in different places
    (84 of 174 values of the tiny sambay_decoder then differ in their last
    bit)."""
    import paddle_tpu as fluid

    fluid.reset_default_env()
    spec = model(config(**sizes))
    pairs = fluid.append_backward(spec.loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    compiled, *args = exe.capture_program(
        fluid.default_main_program(), feed=spec.synthetic_batch(2, seed=5),
        fetch_list=[spec.loss] + [g for _, g in pairs])
    got = jax.jit(compiled.raw_fn).lower(*args).compile(compiler_options={
        "xla_disable_hlo_passes": "fusion,cpu-instruction-fusion"})(*args)
    kept = sum(op.type == "kept" for b in spec.loss.block.program.blocks
               for op in b.desc.ops)
    return kept, [np.asarray(x) for x in jax.tree_util.tree_leaves(got)]


@once_a_program
def tiny_step(model, config, tagged, **sizes):
    """(text, spans) of `model(config(**sizes))`'s step as it lowers for a
    TPU (test_recompute_keep._step_for_the_tpu; the kernel-backed ops on
    their jax.numpy engines at tiny widths), with the tags or without."""
    from test_recompute_keep import _step_for_the_tpu

    lower = _step_for_the_tpu if tagged else functools.partial(
        untagged, _step_for_the_tpu)
    return lower(model, config(**sizes), span_names=("recurrence.lower",))


def kept_is_the_untagged_program_bit_for_bit(model, config, tags, least,
                                             **sizes):
    """The kept value is the first forward's own output, which the
    recomputation would have made again from the same operands: the loss and
    every parameter's gradient bit for bit; `tags` ops `kept` in the
    program, more than `least` values and as many of them not zero."""
    n, got = loss_and_gradients(model, config, **sizes)
    none, want = untagged(loss_and_gradients, model, config, **sizes)
    assert (n, none) == (tags, 0)
    assert len(got) == len(want) > least
    assert sum(float(np.abs(x).max()) > 0 for x in got) > least
    for a, b in zip(got, want):
        assert np.array_equal(a, b)


def kept_products_are_lowered_once(model, config, widths, kept_a_unit,
                                   **sizes):
    """`widths`: {a tagged product's width: the layers that make it}; the
    recomputed step lowers each once a layer where the untagged step has
    two, every unit keeps `kept_a_unit` values more, and what a unit saves
    is the bf16 the product wrote (the AMP keep tier of a step for a TPU),
    nothing of the width made in fp32."""
    S = sizes["max_length"]
    (text, spans), (bare, bare_spans) = (
        tiny_step(model, config, tagged, **sizes, use_recompute=True)
        for tagged in (True, False))
    for width, makers in widths.items():
        assert first_products(text, S, width) == makers, width
        assert first_products(bare, S, width) == 2 * makers, width
        assert re.search(rf"-> tensor<1x{S}x{width}xbf16>", text)
        assert not re.search(rf"tensor<1x{S}x{width}xf32>", text)
    assert [s["kept"] - b["kept"] for s, b in zip(
        spans["recurrence.lower"], bare_spans["recurrence.lower"])] \
        == list(kept_a_unit)
    assert all(s["recompute"] == 1 for s in spans["recurrence.lower"])


def kept_adds_no_operation_without_recompute(model, config, kept_a_unit,
                                             **sizes):
    """use_recompute false: the tags are counted (`kept` higher by
    `kept_a_unit`) and there is no checkpoint for them to speak to: the
    step's StableHLO is the untagged step's, operation for operation."""
    (text, spans), (bare, bare_spans) = (
        tiny_step(model, config, tagged, **sizes, use_recompute=False)
        for tagged in (True, False))
    assert [(s["recompute"], s["kept"] - b["kept"]) for s, b in zip(
        spans["recurrence.lower"], bare_spans["recurrence.lower"])] \
        == [(0, k) for k in kept_a_unit]
    # (a private function's number goes by what was traced before it)
    numbers = re.compile(r"@(\w+?)_\d+\b")
    assert numbers.sub(r"@\1", text) == numbers.sub(r"@\1", bare)
