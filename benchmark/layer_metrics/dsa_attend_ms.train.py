"""Device ms a traced step in the sparse attention's core (name scope
`dsa.attend`: the masked block kernels, forward, the heads' summed
probabilities and the one backward kernel, with the live-block vectors and the
masks' casts around them), forward, recomputed forward and backward (kind
train), from the trace.  None where the program has no such scope."""

from benchmark.harness import scope_time


def read(obs):
    return scope_time.per_step_ms(obs, "dsa.attend")
