"""Device ms a traced step under the name scope `eva.pool` (the op
`eva_attention`'s pooling: every chunk of 16 rotated keys and their values
weighted by two softmaxes over the chunk against a head's learned mu and
phi, one summary key and one summary value out), forward and backward (kind
train), from the trace; the layer's recomputation runs no second forward of
it (the compiler merges it with the first).  None where the program has no
such scope."""

from benchmark.harness import scope_time


def read(obs):
    return scope_time.per_step_ms(obs, "eva.pool")
