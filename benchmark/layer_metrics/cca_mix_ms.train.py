"""Device ms a traced step in compressed convolutional attention's sequence
mixing (name scope `cca.mix`, the op `compressed_conv_qkv`: the two causal
convolutions over [q ; k], the q-k mean, the norm with the key temperature,
the rotary over half a head, the value's shift and the turn to heads first;
the projections and the flash kernels are outside), forward, recomputed
forward where the compiler leaves it and backward (kind train), from the
trace.  None where the program has no such scope."""

from benchmark.harness import scope_time


def read(obs):
    return scope_time.per_step_ms(obs, "cca.mix")
