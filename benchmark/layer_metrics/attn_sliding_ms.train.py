"""Device ms a traced step in the sliding layers' attention (name scope
`attn.sliding`: the flash kernels of `fused_attention` with a window, forward
(and the recomputed one where the compiler leaves it: on the chip it merges
it with the first) and the backward's calls with their glue: rowsum(dO * O),
a group's dK and dV added up, the chunks' slices; the projections and the
rotary are outside), kind train, from the trace.  None where the program has
no such scope."""

from benchmark.harness import scope_time


def read(obs):
    return scope_time.per_step_ms(obs, "attn.sliding")
