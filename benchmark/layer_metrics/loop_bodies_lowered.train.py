"""How many times the step program lowers the body of its `recurrence` op:
the `bodies_lowered` count of the `recurrence.lower` span
(paddle_tpu/ops/control_flow_ops.py), 1 where the trips are one lax.scan body
(kind train).

The span is written while the step is lowered, which is set-up and long over
when a reader runs, and with FLAGS_observability off it is kept nowhere.  So
the reader lowers the same step program once more, abstractly
(`jax.eval_shape` of the executor's own captured program: nothing compiles,
nothing runs on the device), with the flag on for that moment, and reads the
spans this leaves.  None where the program has no `recurrence` op."""


def read(obs):
    if obs.get("kind") != "train" or not obs.get("samples_per_step"):
        return None
    import jax
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu import observability
    from paddle_tpu.core.proto import dtype_to_numpy

    program = fluid.default_main_program()
    block = program.global_block()
    if not any(op.type == "recurrence" for op in block.desc.ops):
        return None
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
        spans = [s for s in observability.default_tracer().spans()[before:]
                 if s.name == "recurrence.lower"]
    finally:
        if not was_on:
            observability.disable()
    if not spans:
        return None
    return max(s.args["bodies_lowered"] for s in spans)
