"""What the decoder test files share: one build a distinct program, and the
plain reference's step as one compile."""

import functools

import jax
import jax.numpy as jnp


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
