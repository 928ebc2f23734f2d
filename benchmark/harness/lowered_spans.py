"""The spans a training step leaves while it is lowered.

A span written at lowering (`flash.plan`, `attn.lower`, ...) is set-up and
long over when a reader runs, and with FLAGS_observability off it is kept
nowhere.  So the step program is lowered once more, abstractly
(`jax.eval_shape` of the executor's own captured program: nothing compiles,
nothing runs on the device), with the flag on for that moment, as
layer_metrics/loop_bodies_lowered.train.py does for its one span."""

from __future__ import annotations


def of_step(obs, names) -> dict:
    """{name: [the args of every span of that name]} of one abstract
    lowering of the default main program's step; {} where the run is no
    training run."""
    if obs.get("kind") != "train" or not obs.get("samples_per_step"):
        return {}
    import jax
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu import observability
    from paddle_tpu.core.proto import dtype_to_numpy

    program = fluid.default_main_program()
    block = program.global_block()
    rows = obs["samples_per_step"] // obs.get("chips", 1)
    made = {n for op in block.desc.ops for n in op.output_arg_names()}
    feed = {name: np.zeros([rows] + list(v.shape[1:]),
                           dtype_to_numpy(v.dtype))
            for name, v in block.vars.items()
            if name not in made and not v.persistable
            and list(v.shape[:1]) == [-1]}
    place = fluid.TPUPlace() if obs.get("platform") == "tpu" \
        else fluid.CPUPlace()
    was_on = observability.enabled()
    before = len(observability.default_tracer().spans())
    observability.enable()
    try:
        compiled, feed_vals, state_vals, rng = fluid.Executor(
            place).capture_program(program, feed=feed)
        jax.eval_shape(compiled.raw_fn, feed_vals, state_vals, rng)
        spans = observability.default_tracer().spans()[before:]
    finally:
        if not was_on:
            observability.disable()
    return {name: [dict(s.args) for s in spans if s.name == name]
            for name in names}
