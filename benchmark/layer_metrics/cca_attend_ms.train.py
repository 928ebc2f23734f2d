"""Device ms a traced step in compressed convolutional attention's scores
(name scope `cca.attend`: the flash kernels of `fused_attention` at 8 query
heads on 2 key/value heads, forward, the recomputed forward (it runs in
this cell: the compiler does not merge it with the first) and the
backward's calls with their glue: rowsum(dO * O), a group's dK and dV added
up, the chunks' slices; the projections and `cca.mix` are outside), kind
train, from the trace.  None where the program has no such scope."""

from benchmark.harness import scope_time


def read(obs):
    return scope_time.per_step_ms(obs, "cca.attend")
