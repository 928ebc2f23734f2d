"""Device ms a traced step in the operations of the shared experts (name scope `moe.shared`: a gated MLP over every token), forward, recomputed
forward and backward (kind train), from the trace.  None where the program
has no such scope."""

from benchmark.harness import scope_time


def read(obs):
    return scope_time.per_step_ms(obs, "moe.shared")
