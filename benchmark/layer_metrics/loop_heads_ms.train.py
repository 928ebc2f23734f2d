"""Device ms a traced step in the operations under the program's name scope
`loop.heads`: the R-fold head matmul, its cross entropy, the exit gate and
the loss, forward and backward (kind train), from the trace.  None where the
program has no such scope."""

from benchmark.harness import scope_time


def read(obs):
    return scope_time.per_step_ms(obs, "loop.heads")
